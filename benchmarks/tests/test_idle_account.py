"""The account of the chip's idle time and of the worker's host time
(harness/idle_account.py and the ten layer metrics on top of it): the
partition on fabricated events, that its shares and the remainder sum to
what `spans.idle_split` sorts three ways, the five span-buffer values on
a hand-made buffer, 0 and not None where a marked program's window holds
nothing of a kind, None for a program without the spans, and the ten
entries at the end of `per_layer`."""
import pytest

from harness import idle_account, manifest, spans, trace
from test_gap_metrics import _buffer

DEVICE = ['idle_in_program_share.tpot', 'idle_empty_share.tpot',
          'idle_admit_share.tpot', 'idle_cache_share.tpot',
          'idle_dispatch_share.tpot']
HOST = ['engine_empty_share.tpot', 'admit_ms_mean.tpot',
        'prefix_match_ms_mean.tpot', 'evict_ms_per_s.tpot',
        'evict_scan_per_page.tpot']
SERVING = ['gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason',
           'axk1_serve_docfollow', 'granite4hs_serve_sessions',
           'sthink21b_serve_mixed', 'solar2_serve_chat_shared']
TPU, CPU = '/device:TPU:0', '/host:CPU'
WINDOW_S = 13e-6                # the fabricated events' 13 000 ns


def _events(inside=True):
    """Seven executions of 1000 ns, 1000 ns apart, each one op but the
    first, whose two ops leave a gap of 300 ns INSIDE it (with `inside`
    off the first execution ends before the gap: the same gap then lies
    between two), and the host's `pt.` spans over the six gaps."""
    ev = [(TPU, trace.MODULES_LINE, 'jit_step(1)', 0,
           1000 if inside else 300),
          (TPU, trace.OPS_LINE, '%fusion.1 = f32[8]', 0, 300),
          (TPU, trace.OPS_LINE, '%fusion.2 = f32[8]', 600, 400)]
    for k in range(1, 7):
        ev += [(TPU, trace.MODULES_LINE, 'jit_step(1)', 2000 * k, 1000),
               (TPU, trace.OPS_LINE, '%fusion.3 = f32[8]', 2000 * k, 1000)]

    def host(name, start, end):
        ev.append((CPU, 'worker', 'pt.' + name, start, end - start))
    host('serve.admit', 200, 700)               # over the gap in A
    host('serve.admit', 1100, 1900)             # A..B: a stream opens
    host('paged.open', 1200, 1800)
    host('serve.idle', 3100, 3950)              # B..C: an empty engine
    host('paged.decode.tables', 5100, 5900)     # C..D: a pool ran dry
    host('paged.prefix.evict', 5300, 5700)
    host('paged.decode.tables', 7100, 7900)     # D..E: too slow to feed
    host('paged.decode.fetch', 9100, 9900)      # E..F: the remainder
    ev.append((CPU, 'submitter', 'bench.submit', 11100, 800))   # F..G: none
    return ev


def _pct(ns):
    return 100.0 * ns / 13000


def test_a_gap_inside_an_execution_is_the_devices_whatever_the_host_did():
    p = idle_account.partition(_events(), WINDOW_S)
    assert p == pytest.approx({
        'in_program': _pct(300), 'admit': _pct(1000), 'empty': _pct(1000),
        'cache': _pct(1000), 'dispatch': _pct(1000), 'rest': _pct(2000),
        'between_ops': _pct(6300)})
    # the same gap between two executions goes to what the host was in
    q = idle_account.partition(_events(inside=False), WINDOW_S)
    assert q['in_program'] == 0.0 and q['admit'] == pytest.approx(_pct(1300))
    assert {k: q[k] for k in q if k not in ('in_program', 'admit')} \
        == {k: p[k] for k in p if k not in ('in_program', 'admit')}


@pytest.mark.parametrize('inside', [True, False])
def test_the_five_and_the_remainder_sum_to_the_idle_time_between_ops(inside):
    events = _events(inside)
    p = idle_account.partition(events, WINDOW_S)
    three = spans.idle_split(events, WINDOW_S)
    assert sum(p[k] for k in idle_account.SHARES) + p['rest'] \
        == pytest.approx(p['between_ops']) \
        == pytest.approx(three['feed'] + three['fetch'] + three['elsewhere'])
    assert all(p[k] >= 0.0 for k in p)
    # the eviction under `*.tables` is the cache's here and no longer feed
    assert three['feed'] == pytest.approx(_pct(1000))


def test_on_a_recorded_capture_the_partition_sums_to_the_three_way_split():
    """tests/data/serve_chat_spans_v5e.json: 0.28 s of the chat cell on a
    v5e, recorded at PR 24 (no span of this account in it: what the
    host's share is made of there is dispatch)."""
    import json
    import os
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           'data', 'serve_chat_spans_v5e.json')) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec['events']]
    p = idle_account.partition(events, rec['window_s'])
    three = spans.idle_split(events, rec['window_s'])
    assert p['between_ops'] == pytest.approx(
        three['feed'] + three['fetch'] + three['elsewhere'], rel=1e-9)
    assert p['between_ops'] > 8.0 and p['dispatch'] > 0.99 * p['between_ops']
    assert 0.0 < p['in_program'] < 0.01
    assert p['empty'] == p['admit'] == p['cache'] == 0.0


def test_the_innermost_span_is_the_one_that_started_last():
    host = [(0, 100, 'a'), (10, 50, 'b'), (20, 30, 'c'), (60, 90, 'd')]
    assert idle_account._innermost(host, [5, 25, 40, 55, 70, 95, 200]) \
        == ['a', 'c', 'b', 'a', 'd', 'a', None]


def test_a_capture_without_ops_or_spans_reads_zero_and_no_window_nothing():
    events = _events()
    zeros = dict.fromkeys(idle_account.SHARES + ('rest', 'between_ops'), 0.0)
    assert idle_account.partition([], WINDOW_S) == zeros
    assert idle_account.partition(
        [e for e in events if e[0] == CPU], WINDOW_S) == zeros
    # no program span in the capture: what lies between two executions
    # is nobody's, what lies inside one still the device's
    bare = idle_account.partition(
        [e for e in events if not e[2].startswith('pt.')], WINDOW_S)
    assert bare == pytest.approx(dict(
        zeros, in_program=_pct(300), rest=_pct(6000),
        between_ops=_pct(6300)))
    assert idle_account.partition(events, 0.0) is None


def _marked_buffer():
    """test_gap_metrics' buffer (judged window [10.05, 12.72], worker
    thread 2) with what a marked program adds: an empty stay, a pass that
    admitted two streams, their matches and three evictions."""
    buf, plan = _buffer()
    sid = iter(range(5000, 6000))

    def add(name, t0, t1, psid=None, **attrs):
        buf.append(dict(attrs, name=name, kind='host', sid=next(sid),
                        psid=psid, t0=t0, t1=t1, tid=2))
        return buf[-1]['sid']
    add('serve.idle', 9.5, 10.07)               # 0.02 s of it inside
    add('serve.idle', 11.0, 11.5)
    add('serve.admit', 10.1, 10.1001, psid=1001, admitted=0)
    admit = add('serve.admit', 10.2, 10.206, psid=1002, admitted=2)
    for t0 in (10.2005, 10.2035):
        opened = add('paged.open', t0, t0 + 0.002, psid=admit,
                     prompt_tokens=40, shared_tokens=16)
        add('paged.prefix.match', t0, t0 + 0.001, psid=opened, pages=9,
            shared_tokens=16)
    tick = add('serve.prefill_tick', 10.301, 10.32, psid=1003)
    tables = add('paged.prefill.tables', 10.302, 10.31, psid=tick)
    add('paged.prefix.evict', 10.303, 10.304, psid=tables, pool='full',
        scanned=500, freed=1)
    add('paged.prefix.evict', 10.304, 10.306, psid=tables, pool='full',
        scanned=499, freed=0)
    dtables = add('paged.decode.tables', 10.401, 10.403, psid=1004)
    add('paged.prefix.evict', 10.4015, 10.4025, psid=dtables, pool='window',
        scanned=501, freed=1)
    return buf, plan


def test_the_five_span_buffer_values_to_the_digit():
    buf, plan = _marked_buffer()
    view = idle_account.host_view(buf, (10.05, 12.72))
    assert view['workers'] == 1 and view['window_s'] == pytest.approx(2.67)
    assert view['idle_s'] == pytest.approx(0.52)
    assert (view['admitted'], view['matches'], view['evictions'],
            view['scanned'], view['freed']) == (2, 2, 3, 1500, 2)
    assert view['evict_s'] == pytest.approx(0.004)
    assert view['evict_tick_s'] == pytest.approx(0.003)
    # the parts are a partition of the window
    assert sum(view['parts'].values()) == pytest.approx(2.67)
    assert view['parts']['serve.admit'] == pytest.approx(0.0061)
    assert view['parts']['serve.prefill_tick'] == pytest.approx(0.019)
    values = idle_account.host_values(view)
    assert values == pytest.approx({
        'engine_empty_share': 100.0 * 0.52 / 2.67,
        'admit_ms_mean': 3.0, 'prefix_match_ms_mean': 1.0,
        'evict_ms_per_s': 4.0 / 2.67, 'evict_scan_per_page': 750.0})


def _read(names, run):
    man = manifest.load()
    return [manifest.layer_metric(man, n).read(run) for n in names]


def test_the_readers_read_one_account_a_run(monkeypatch):
    buf, plan = _marked_buffer()
    calls = []
    monkeypatch.setattr(spans, 'program_spans',
                        lambda: calls.append('spans') or buf)
    monkeypatch.setattr(trace, 'read_xplane',
                        lambda d: calls.append('xplane') or _events())
    run = {'plan': plan, 'trace': {'window_s': WINDOW_S}}
    assert _read(DEVICE, run) == pytest.approx(
        [_pct(300)] + [_pct(1000)] * 4)
    assert _read(HOST, run) == pytest.approx(
        [100.0 * 0.52 / 2.67, 3.0, 1.0, 4.0 / 2.67, 750.0])
    # the account reads each once; `gaps.of_run` reads the buffer too
    assert calls.count('xplane') == 1 and calls.count('spans') == 2


def test_a_window_that_holds_nothing_of_a_kind_reads_zero_not_none(
        monkeypatch):
    buf, plan = _buffer()
    buf.append(dict(name='serve.admit', kind='host', sid=5000, psid=None,
                    t0=5.0, t1=5.001, tid=2, admitted=1))   # the warm-up's
    one_program = [e for e in _events() if e[3] < 1000]
    monkeypatch.setattr(spans, 'program_spans', lambda: buf)
    monkeypatch.setattr(trace, 'read_xplane', lambda d: one_program)
    run = {'plan': plan, 'trace': {'window_s': WINDOW_S}}
    assert _read(DEVICE, run) == [pytest.approx(_pct(300)), 0.0, 0.0, 0.0,
                                  0.0]
    assert _read(HOST, run) == [0.0] * 5
    # an untraced run (tools/host_account.py) has the five of the buffer
    assert _read(HOST, {'plan': plan}) == [0.0] * 5
    assert _read(DEVICE, {'plan': plan}) == [None] * 5


def test_a_program_without_the_spans_is_left_out_and_nothing_raises(
        monkeypatch):
    buf, plan = _buffer()
    buf.append(dict(name='serve.admit', kind='host', sid=5000, psid=None,
                    t0=5.0, t1=5.001, tid=2))       # the parent's: no attr
    monkeypatch.setattr(spans, 'program_spans', lambda: buf)
    monkeypatch.setattr(trace, 'read_xplane', lambda d: _events())
    run = {'plan': plan, 'trace': {'window_s': WINDOW_S}}
    assert _read(HOST, run) == [None] * 5
    # what needs no new span is read; what names one is not
    in_program, empty, admit, cache, dispatch = _read(DEVICE, run)
    assert (empty, admit, cache) == (None,) * 3
    assert in_program == pytest.approx(_pct(300)) and dispatch > 0
    # no buffer at all and an empty capture: nothing of the host's
    monkeypatch.setattr(spans, 'program_spans', lambda: [])
    monkeypatch.setattr(trace, 'read_xplane', lambda d: [])
    run = {'plan': plan, 'trace': {'window_s': WINDOW_S}}
    assert _read(DEVICE + HOST, run) == [0.0] * 5 + [None] * 5


def test_the_ten_entries_stand_at_the_end_and_agree_with_their_readers():
    """In order, together, behind the older ones (later PRs append behind
    them)."""
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    at = names.index(DEVICE[0])
    assert at >= 87 and names[at:at + 10] == DEVICE + HOST
    cache = 'cache (serving/paging.py)'
    engine = 'engine (serving/engine.py)'
    want = dict(zip(DEVICE, [('%', 'device_trace', 'device')] * 5))
    want.update(zip(HOST, [('%', 'program_span', engine),
                           ('ms', 'program_span', engine),
                           ('ms', 'program_span', cache),
                           ('ms/s', 'program_span', cache),
                           ('entries', 'program_span', cache)]))
    older = {m['layer'] for m in man['per_layer'][:at]}
    for e in man['per_layer'][at:at + 10]:
        mod = manifest.layer_metric(man, e['name'])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == \
            (e['layer'], e['unit'], e['better'], e['source']), e['name']
        assert (e['unit'], e['source'], e['layer']) == want[e['name']]
        assert e['layer'] in older
        assert (e['better'], e['moves']) == ('lower', 'tpot_p50_ms')
        assert set(e['workloads']) <= set(SERVING)
        assert e['workloads'] == [c for c in SERVING if c in e['workloads']]
        assert set(e) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    cells = {e['name']: e['workloads'] for e in man['per_layer'][at:at + 10]}
    for name in ('idle_in_program_share.tpot', 'idle_empty_share.tpot',
                 'idle_admit_share.tpot', 'idle_dispatch_share.tpot',
                 'engine_empty_share.tpot', 'admit_ms_mean.tpot'):
        assert cells[name] == SERVING
    # the cells whose open_stream asks the cache; the pools that hold a
    # cached corpus; those and the two whose register_state gives rows up
    assert cells['prefix_match_ms_mean.tpot'] == [
        c for c in SERVING if c not in ('olmohyb_serve_long',
                                        'nemo3s_serve_reason')]
    assert cells['evict_ms_per_s.tpot'] == cells['evict_scan_per_page.tpot']
    assert {'axk1_serve_docfollow', 'sthink21b_serve_mixed'} \
        <= set(cells['evict_ms_per_s.tpot'])
    assert set(cells['idle_cache_share.tpot']) == \
        set(cells['evict_ms_per_s.tpot']) | {'granite4hs_serve_sessions',
                                             'solar2_serve_chat_shared'}
