"""Where the serving loop's host time goes, in the program's own spans
(serving/engine.py, serving/paged.py; two integers of serving/paging.py):

- `serve.idle`: one span a stay of a worker that has no lane and an
  empty queue, and the counter serving.loop.idle_seconds beside it;
- `serve.admit` says what it did (`admitted`), and a stream's opening is
  `paged.open` > `paged.prefix.match` under it;
- `paged.prefix.register` inside the `paged.prefill.book` of a prompt's
  last chunk, and of no other;
- `paged.prefix.evict` under the `*.tables` span that asked for the
  page, with what the scan looked at, and the serving.prefix.* counters;
- a pass with lanes live, no arrival and no eviction opens the spans it
  opened before all of this, name for name;
- with the registry off nothing is recorded and no counter moves.

By family where the path differs: a plain page table, a second table
with a window (models/smallthinker.py), pages and snapshot rows
(models/hybrid.py)."""
import time

import pytest

from paddle_tpu.obs import telemetry, trace
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.paging import PrefixCache

import test_hybrid_serving
import test_smallthinker
from test_decode_pipeline import GPT2
from test_paged import _save_lm
from test_spans import registry_on          # noqa: F401 (a fixture)

FAMILIES = ['plain', 'window', 'rows']
# what a pass with lanes live, no arrival and no eviction opened at the
# parent of the PR that brought the spans above (the pipelined loop: the
# fetch and the accept are the step before's)
STEADY = ['serve.iter', 'serve.admit', 'serve.prefill_tick', 'serve.pack',
          'paged.decode.tables', 'exe.run', 'exe.feed', 'exe.prepare',
          'device_segment', 'paged.decode.book', 'paged.decode.fetch',
          'serve.accept']


@pytest.fixture(scope='module')
def served(tmp_path_factory):
    """family -> (predictor, token ids to cut prompts from, the
    decoder's sizes), each built when first asked for."""
    built = {}

    def get(family):
        if family not in built:
            tmp = tmp_path_factory.mktemp('host_account_' + family)
            if family == 'plain':
                pred = _save_lm(tmp, GPT2, 9)
                toks = list(range(1, 49))
                sizes = dict(slots=3, page_tokens=4, kv_pages=40,
                             prefill_chunk=8)
            elif family == 'window':
                pred, toks, _ = test_smallthinker._build(tmp)
                sizes = dict(slots=3, page_tokens=4, kv_pages=60,
                             window_pages=40, prefill_chunk=8)
            else:
                pred, toks, _ = test_hybrid_serving._build(tmp)
                sizes = dict(slots=3, page_tokens=4, kv_pages=40,
                             prefill_chunk=8, snapshot_rows=3)
            built[family] = pred, [int(t) for t in toks], sizes
        return built[family]
    return get


def _decoder(served, family, **kw):
    pred, toks, sizes = served(family)
    return pred.prepare_decoding(**dict(sizes, **kw)), toks


def _serve(dec, asks, pause=0.0):
    """The (prompt, budget) pairs of `asks` through an engine over
    `dec`, ONE AT A TIME (each is done, and its prompt registered,
    before the next arrives), with `pause` seconds of an empty engine
    before each and after the last."""
    engine = ServingEngine(dec, idle_wait=0.01).start()
    try:
        for prompt, budget in asks:
            time.sleep(pause)
            engine.submit(prompt, max_new_tokens=budget).result(240)
        time.sleep(pause)
    finally:
        assert engine.stop(drain=True, timeout=30.0)


def _named(name):
    return [s for s in trace.spans() if s['name'] == name]


def _counter(name):
    return telemetry.snapshot()['counters'].get(name, 0)


def _seconds(spans):
    return sum(s['t1'] - s['t0'] for s in spans)


# --------------------------------------------------------------------------
# (a) an empty engine
# --------------------------------------------------------------------------

def test_one_idle_span_a_stay_and_none_while_a_lane_is_live(served,
                                                            registry_on):
    dec, toks = _decoder(served, 'plain')
    # 0.3 s is thirty wake-ups of `idle_wait`: still one span a stay
    _serve(dec, [(toks[:9], 12)], pause=0.3)
    idle, passes = _named('serve.idle'), _named('serve.iter')
    assert len(idle) == 2 and all(s['psid'] is None for s in idle)
    assert all(0.25 < s['t1'] - s['t0'] < 5.0 for s in idle)
    assert {s['tid'] for s in idle} == {s['tid'] for s in passes}
    # the request's passes lie between the two stays, none inside one
    assert idle[0]['t1'] <= passes[0]['t0'] \
        and passes[-1]['t1'] <= idle[1]['t0']
    life = idle[1]['t1'] - idle[0]['t0']
    assert _seconds(idle) + _seconds(passes) == pytest.approx(life, rel=0.02)
    assert _counter('serving.loop.idle_seconds') \
        == pytest.approx(_seconds(idle), abs=2e-3)
    assert _counter('serving.loop.seconds') \
        == pytest.approx(_seconds(passes), abs=2e-3 * len(passes))


# --------------------------------------------------------------------------
# (b) admission and registration
# --------------------------------------------------------------------------

@pytest.mark.parametrize('family', FAMILIES)
def test_admission_says_what_it_did(served, registry_on, family):
    dec, toks = _decoder(served, family)
    pt = dec.page_tokens
    # three prompts, the third a follow-up of the first (21 tokens are
    # three chunks of 8): its stream opens on what the first registered
    prompts = [toks[:21], toks[30:41], toks[:21] + toks[41:47]]
    _serve(dec, [(p, 3) for p in prompts])
    admits = _named('serve.admit')
    assert sum(s['admitted'] for s in admits) == len(prompts)
    assert {s['admitted'] for s in admits} == {0, 1}
    opens, matches = _named('paged.open'), _named('paged.prefix.match')
    admitting = {s['sid'] for s in admits if s['admitted']}
    assert [s['psid'] in admitting for s in opens] == [True] * 3
    assert [m['psid'] for m in matches] == [s['sid'] for s in opens]
    assert [s['prompt_tokens'] for s in opens] == [len(p) for p in prompts]
    assert [m['pages'] for m in matches] \
        == [(len(p) - 1) // pt for p in prompts]
    shared = [s['shared_tokens'] for s in opens]
    assert shared == [m['shared_tokens'] for m in matches]
    assert shared[:2] == [0, 0] and 0 < shared[2] <= 21
    # once a prompt, in the book of its last chunk and of no other
    books = {s['sid']: s for s in _named('paged.prefill.book')}
    registers = _named('paged.prefix.register')
    assert len(registers) == len(prompts) < len(books)
    assert all(r['psid'] in books for r in registers)
    assert [r['tokens'] for r in registers] == [len(p) for p in prompts]
    assert [r['pages'] for r in registers] == [len(p) // pt for p in prompts]
    last_books = [max((b for b in books.values() if b['t0'] < r['t1']),
                      key=lambda b: b['t0'])['sid'] for r in registers]
    assert last_books == [r['psid'] for r in registers]
    if family == 'rows':
        assert [r['row'] for r in registers] == [1, 1, 1]
    else:
        assert not any('row' in r for r in registers)


# --------------------------------------------------------------------------
# (c) evictions
# --------------------------------------------------------------------------

@pytest.mark.parametrize('pool', ['full', 'window'])
def test_a_pool_too_small_for_its_traffic_leaves_evict_spans(
        served, registry_on, monkeypatch, pool):
    held = []
    method = 'evict_one' if pool == 'full' else 'evict_window_one'
    evict = getattr(PrefixCache, method)

    def spy(cache):
        held.append(len(cache))
        return evict(cache)
    monkeypatch.setattr(PrefixCache, method, spy)
    if pool == 'full':
        dec, toks = _decoder(served, 'plain', kv_pages=12)
    else:
        dec, toks = _decoder(served, 'window', window_pages=8)
    # distinct prompts, so what each leaves in the cache is of no use to
    # the next, which needs the pages
    _serve(dec, [(toks[i:i + 17], 6) for i in (0, 5, 10, 15, 20)])
    spans = _named('paged.prefix.evict')
    assert spans and {s['pool'] for s in spans} == {pool}
    assert [s['scanned'] for s in spans] == held and min(held) > 0
    assert {s['freed'] for s in spans} == {1}
    tables = {s['sid'] for s in trace.spans()
              if s['name'].endswith('.tables')}
    assert all(s['psid'] in tables for s in spans)
    assert _counter('serving.prefix.evictions') == len(spans) \
        == dec._prefix.evictions
    assert _counter('serving.prefix.entries_scanned') == sum(held) \
        == dec._prefix.entries_scanned
    assert _counter('serving.prefix.evict_seconds') \
        == pytest.approx(_seconds(spans), abs=1e-3 * len(spans))


# --------------------------------------------------------------------------
# (d) the steady pass
# --------------------------------------------------------------------------

@pytest.mark.parametrize('family', FAMILIES)
def test_the_steady_pass_opens_the_spans_it_opened_before(
        served, registry_on, family):
    dec, toks = _decoder(served, family)
    _serve(dec, [(toks[:6], 20)])
    spans = trace.spans()
    kids = {}
    for s in spans:
        kids.setdefault(s['psid'], []).append(s)

    def names(span):
        yield span['name'].split(':')[0]
        for k in sorted(kids.get(span['sid'], ()), key=lambda s: s['t0']):
            yield from names(k)
    steady = [list(names(s)) for s in spans if s['name'] == 'serve.iter'
              and s['step'] and not s['chunk'] and s['lanes'] == s['ready']]
    # all of the stream's plain steps but the one behind its last chunk's
    # pass, which fetches the first token too
    assert len(steady) >= 15
    assert [names_ for names_ in steady if names_ != STEADY] == []


# --------------------------------------------------------------------------
# (e) the registry off
# --------------------------------------------------------------------------

def test_with_the_registry_off_nothing_is_recorded(served):
    telemetry.reset()
    trace.clear()
    assert not telemetry.enabled()
    dec, toks = _decoder(served, 'plain', kv_pages=12)
    _serve(dec, [(toks[i:i + 17], 6) for i in (0, 5, 10, 15)], pause=0.02)
    assert dec._prefix.evictions > 0 and dec._prefix.entries_scanned > 0
    assert trace.spans() == []
    assert not any(telemetry.snapshot()['counters'].values())
