"""The readers of the program's own spans (harness/spans.py and the
eleven layer metrics on top of it): their arithmetic on a hand-made span
buffer, the idle split on a recorded capture of the serving cell, and
that a program without spans gives nothing and raises nothing."""
import json
import os

import pytest

from harness import manifest, spans, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data')
NEW = ['queue_wait_p90_ms.tpot', 'prefill_wall_p90_ms.tpot',
       'token_gap_p99_ms.tpot', 'decode_prep_ms_mean.tpot',
       'decode_fetch_ms_mean.tpot', 'idle_feed_share.tpot',
       'idle_fetch_share.tpot', 'idle_elsewhere_share.tpot',
       'run_host_ms_p50.train', 'run_host_ms_max.train',
       'reader_pop_ms_max.train']


class _Buffer:
    """A span buffer made by hand, in the shape of
    `paddle_tpu.obs.trace.spans()`."""

    def __init__(self):
        self.spans, self._sid = [], 0

    def add(self, name, t0, t1, psid=None, kind='host', sid=None, **attrs):
        if sid is None:
            self._sid += 1
            sid = self._sid
        self.spans.append(dict(attrs, name=name, kind=kind, sid=sid,
                               psid=psid, t0=t0, t1=t1, tid=1))
        return sid

    def request(self, rid, n_prompt, max_new, submitted, admitted, first,
                gaps_ms):
        done = first + sum(gaps_ms) / 1e3 + 0.0001
        attrs = dict(n_prompt=n_prompt, max_new_tokens=max_new,
                     n_tokens=len(gaps_ms) + 1, state='DONE')
        for name, t0, t1 in (('serve.queue', submitted, admitted),
                             ('serve.prefill', admitted, first),
                             ('serve.decode', first, done)):
            extra = {'gaps_ms': gaps_ms} if name == 'serve.decode' else {}
            self.add(name, t0, t1, kind='request', sid=1000 + rid,
                     **attrs, **extra)
        return done

    def iteration(self, t, tables, run, book, fetch, prefill=0.0,
                  feed=0.2, prepare=0.1, dispatch=0.3):
        """One worker pass starting at t (seconds); parts in ms. Returns
        (end of the pass, wall ms of its decode call as a wrapper around
        decode_step would time it)."""
        ms = 1e-3
        it = self.add('serve.iter', t, t, lanes=2, ready=2)
        cur = t + 0.01 * ms
        if prefill:
            tick = self.add('serve.prefill_tick', cur, cur + prefill * ms, it)
            self.add('paged.prefill.tables', cur, cur + 0.1 * ms, tick)
            self.add('exe.run', cur + 0.1 * ms, cur + prefill * ms, tick,
                     fingerprint='prefill')
            cur += prefill * ms
        call0 = cur
        self.add('paged.decode.tables', cur, cur + tables * ms, it)
        cur += tables * ms
        r = self.add('exe.run', cur, cur + run * ms, it, fingerprint='decode')
        self.add('exe.feed', cur, cur + feed * ms, r)
        self.add('exe.prepare', cur + feed * ms,
                 cur + (feed + prepare) * ms, r)
        self.add('device_segment:0(93 ops)', cur + (run - dispatch) * ms,
                 cur + run * ms, r)
        cur += run * ms
        self.add('paged.decode.book', cur, cur + book * ms, it)
        cur += book * ms
        self.add('paged.decode.fetch', cur, cur + fetch * ms, it)
        cur += fetch * ms
        wall = (cur - call0) / ms + 0.02      # the wrapper's own overhead
        self.add('serve.accept', cur, cur + 0.05 * ms, it)
        end = cur + 0.06 * ms
        next(s for s in self.spans if s['sid'] == it)['t1'] = end
        return end, wall


def _serving_buffer():
    """Two warm-up requests, four judged, one in the tail; decode calls
    before, while and after the judged run."""
    b = _Buffer()
    b.request(0, 8, 4, 0.0, 0.001, 0.1, [50.0] * 3)
    b.request(1, 8, 4, 0.2, 0.201, 0.3, [50.0] * 3)
    b.iteration(0.05, 9.0, 9.0, 9.0, 9.0)            # warm-up: not counted
    # judged: submitted 10..13 s; queue waits 10, 20, 30, 400 ms;
    # prefill walls 100, 200, 300, 900 ms
    waits = [0.010, 0.020, 0.030, 0.400]
    walls = [0.100, 0.200, 0.300, 0.900]
    gaps = [[50.0] * 99, [60.0] * 99, [55.0] * 98 + [180.0], [52.0] * 99]
    plan = {'judged': 4, 'requests': []}
    last_done = 0.0
    for i in range(4):
        sub = 10.0 + i
        plan['requests'].append({'prompt': [1] * (64 + i), 'max_new': 100,
                                 'due': sub - 10.0})
        last_done = max(last_done, b.request(
            2 + i, 64 + i, 100, sub, sub + waits[i],
            sub + waits[i] + walls[i], gaps[i]))
    plan['requests'].append({'prompt': [1] * 77, 'max_new': 32, 'due': 50.0})
    b.request(6, 77, 32, 60.0, 60.01, 60.2, [50.0] * 31)
    t, walls_ms = 10.0, []
    for k in range(150):
        t, wall = b.iteration(t, tables=0.5 + 0.001 * k, run=2.0, book=0.1,
                              fetch=50.0, prefill=8.0 if k % 10 == 0 else 0.0)
        walls_ms.append(wall)
        t += 0.00002
    assert t < last_done
    b.iteration(last_done + 1.0, 9.0, 9.0, 9.0, 9.0)  # the tail: not counted
    return b.spans, plan, walls_ms


def _run(spans_, plan, counters=None, traced=None):
    return {'plan': plan, 'counters': counters or {}, 'trace': traced,
            '_program_spans': {
                'serving': spans.serving_view(spans_, plan)
                if 'judged' in plan else None,
                'training': spans.training_view(
                    spans_, (counters or {}).get('steps')),
                'idle': traced}}


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def test_every_new_metric_has_its_reader_and_its_entry():
    man = manifest.check(manifest.load())
    entries = {m['name']: m for m in man['per_layer']}
    for name in NEW:
        mod = manifest.layer_metric(man, name)
        e = entries[name]
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == \
            (e['layer'], e['unit'], e['better'], e['source']), name
        assert e['better'] == 'lower'
        assert e['source'] == ('device_trace' if name.startswith('idle_')
                               else 'program_span')
    # the entries stand together in the issue's order, behind the older
    # ones; not "last": later PRs append behind them
    names = [m['name'] for m in man['per_layer']]
    at = names.index(NEW[0])
    assert at > 0 and names[at:at + len(NEW)] == NEW


def test_the_judged_requests_are_lined_up_with_the_plan():
    spans_, plan, _ = _serving_buffer()
    view = spans.serving_view(spans_, plan)
    assert view['judged'] == 4
    assert view['queue_ms'] == pytest.approx([10.0, 20.0, 30.0, 400.0])
    assert view['prefill_ms'] == pytest.approx([100.0, 200.0, 300.0, 900.0])
    # queue wait + prefill wall IS first token - submitted, per request
    assert [q + p for q, p in zip(view['queue_ms'], view['prefill_ms'])] == \
        pytest.approx(view['ttft_ms'], abs=1e-9)
    assert len(view['gaps_ms']) == 4 * 99
    assert len(view['decode_calls']) == 150      # warm-up and tail left out
    # a plan the process did not run: nothing, not a guess
    other = dict(plan, requests=[dict(r, max_new=r['max_new'] + 1)
                                 for r in plan['requests']])
    assert spans.serving_view(spans_, other) is None
    assert spans.serving_view([], plan) is None


def test_serving_readers_arithmetic():
    spans_, plan, walls_ms = _serving_buffer()
    run = _run(spans_, plan)
    assert _read('queue_wait_p90_ms.tpot', run) == pytest.approx(400.0)
    assert _read('prefill_wall_p90_ms.tpot', run) == pytest.approx(900.0)
    # 396 gaps, nearest rank: the 393rd smallest is one of the 60 ms, the
    # single 180 ms gap is the 396th
    assert _read('token_gap_p99_ms.tpot', run) == pytest.approx(60.0)
    prep = _read('decode_prep_ms_mean.tpot', run)
    fetch = _read('decode_fetch_ms_mean.tpot', run)
    assert prep == pytest.approx(0.5 + 0.001 * 74.5 + 2.0)
    assert fetch == pytest.approx(50.0)
    calls = spans.of_run(run)['serving']['decode_calls']
    book = spans.mean([c['book'] for c in calls])
    assert book == pytest.approx(0.1)
    # the identity the split is for: the three parts are the decode call
    # (what engine_step_ms_mean times from outside), within 2 %
    engine_step = sum(walls_ms) / len(walls_ms)
    assert prep + fetch + book == pytest.approx(engine_step, rel=0.02)
    # and prep's own parts
    assert spans.mean([c['feed'] for c in calls]) == pytest.approx(0.2)
    assert spans.mean([c['prepare'] for c in calls]) == pytest.approx(0.1)
    assert spans.mean([c['dispatch'] for c in calls]) == pytest.approx(0.3)
    assert all(c['run'] == pytest.approx(2.0) for c in calls)


def _training_buffer(steps, warm=4):
    b = _Buffer()
    t = 0.0
    b.add('exe.run', t, t + 5.0, fingerprint='startup')
    for i in range(warm + steps):
        t = 10.0 + 0.3 * i
        ms = 2.0 + (40.0 if i == warm + 7 else 0.0) + 0.01 * i
        r = b.add('exe.run', t, t + ms / 1e3, fingerprint='step')
        b.add('host_op:read', t + 1e-4,
              t + 1e-4 + (0.0305 if i == warm + 7 else 0.0002), r,
              waited_ms=30.0 if i == warm + 7 else 0.1)
        b.add('device_segment:1(900 ops)', t + 5e-4, t + ms / 1e3, r)
    r = b.add('exe.run', 99.0, 99.5, fingerprint='check')   # another fetch
    b.add('host_op:read', 99.0, 99.4, r)
    return b.spans


def test_training_readers_arithmetic():
    steps = 21
    run = _run(_training_buffer(steps), {'seq_len': 8}, {'steps': steps})
    view = spans.of_run(run)['training']
    assert len(view['run_ms']) == len(view['pop_ms']) == steps
    # the warm-up's four steps and the comparison's step are left out
    assert view['run_ms'][0] == pytest.approx(2.0 + 0.04)
    assert _read('run_host_ms_p50.train', run) == pytest.approx(2.0 + 0.15)
    assert _read('run_host_ms_max.train', run) == pytest.approx(42.0 + 0.11)
    assert _read('reader_pop_ms_max.train', run) == pytest.approx(30.5)
    # fewer steps in the buffer than the window counted: nothing
    assert spans.training_view(_training_buffer(5), 21) is None


def test_a_program_without_spans_gives_nothing_and_raises_nothing(
        monkeypatch):
    """The parent of the PR that brought the spans has no buffer (or an
    empty one) and no `pt.` events: every new reader returns None, so
    the metric is left out of its line."""
    import paddle_tpu.obs.trace as program_trace
    monkeypatch.delattr(program_trace, 'spans')
    assert spans.program_spans() == []
    with open(os.path.join(DATA, 'train_step_v5e.json')) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec['events']]
    assert spans.idle_split(events, rec['window_s']) is None
    plan = {'judged': 1, 'requests': [{'prompt': [1], 'max_new': 2}]}
    run = {'plan': plan, 'counters': {'steps': 3}, 'trace': None}
    views = spans.of_run(run)
    assert views == {'serving': None, 'training': None, 'idle': None}
    assert spans.of_run(run) is views              # made once per run
    for name in NEW:
        assert _read(name, run) is None, name


@pytest.fixture(scope='module')
def recorded():
    """0.25 s of a traced window of gpt1b3_serve_chat on a v5e, cut at
    the edges of two program executions (tools/record_spans.py): the
    device's ops and the host's `pt.*` and `bench.*` spans."""
    with open(os.path.join(DATA, 'serve_chat_spans_v5e.json')) as f:
        rec = json.load(f)
    return [tuple(e) for e in rec['events']], rec['window_s']


def test_recorded_idle_split_accounts_for_the_idle_time(recorded):
    events, window_s = recorded
    split = spans.idle_split(events, window_s)
    red = trace.reduce_events(events, window_s)
    idle = 100.0 * (1.0 - red['busy_s'] / window_s)
    assert 5.0 < idle < 40.0
    parts = split['feed'] + split['fetch'] + split['elsewhere']
    # the three shares are the device's idle share, within 1 point
    assert parts == pytest.approx(idle, abs=1.0)
    assert min(split['feed'], split['fetch'], split['elsewhere']) >= 0.0
    assert split['feed'] > 0.0 and split['fetch'] > 0.0
    # nearly every gap falls inside some program span
    assert split['no_span'] <= 0.10 * parts
    assert 0.0 <= split['no_span'] <= split['elsewhere']
    # the same gaps, as the ledger's breakdown names them from outside
    assert sum(red['gaps'].values()) == \
        pytest.approx(sum(split['gaps'].values()), rel=1e-9)
    for name in split['gaps']:
        assert name.startswith(('in:pt.', 'after:pt.')), name
    # and the readers hand the shares on
    run = _run([], {'requests': []}, traced=split)
    assert _read('idle_feed_share.tpot', run) == split['feed']
    assert _read('idle_fetch_share.tpot', run) == split['fetch']
    assert _read('idle_elsewhere_share.tpot', run) == split['elsewhere']


def test_gap_classes():
    cls = spans._class_of
    for name in ('paged.decode.tables', 'paged.prefill.tables', 'exe.feed',
                 'exe.prepare', 'exe.run', 'device_segment:0(93 ops)'):
        assert cls('in:pt.' + name) == 'feed', name
    for name in ('paged.decode.fetch', 'paged.prefill.fetch', 'exe.fetch'):
        assert cls('in:pt.' + name) == 'fetch', name
    for name in ('in:pt.serve.iter', 'in:pt.serve.accept',
                 'in:pt.paged.decode.book', 'after:pt.serve.iter',
                 'after:pt.paged.decode.fetch', 'no_benchmark_span',
                 'in:bench.decode_step'):
        assert cls(name) == 'elsewhere', name
