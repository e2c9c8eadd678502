"""The seven readers of a token's gap by kind (harness/gaps.py and the
`gap_*`, `*_gap_share` and `host_section_ms_mean` layer metrics): their
values on a hand-made span buffer with known gaps of each kind, None on
a buffer whose `serve.decode` spans lack the lists (the parent's), and
their entries at the end of `per_layer`."""
import pytest

from harness import gaps, manifest

SEVEN = ['gap_plain_ms_p50.tpot', 'gap_chunk_ms_p50.tpot',
         'gap_sync_ms_p50.tpot', 'chunk_gap_share.tpot',
         'sync_gap_share.tpot', 'gap_ms_per_lane.tpot',
         'host_section_ms_mean.tpot']
CELLS = ['gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason']

# (n_prompt, max_new, preemptions, gaps_ms, gap_chunks, gap_lanes, gap_sync)
WARM = (9, 2, 0, [99.0], [1], [1], [1])
JUDGED = [
    # sync (its own first), plain, plain, chunk behind one chunk
    (10, 5, 0, [20.0, 10.0, 10.0, 30.0], [1, 0, 0, 1], [1, 1, 2, 2],
     [1, 0, 0, 0]),
    # sync (first), plain at three lanes, chunk behind two chunks
    (11, 4, 0, [40.0, 12.0, 50.0], [1, 0, 2], [2, 3, 3], [1, 0, 0]),
    # sync (first), sync while already decoding
    (12, 3, 0, [22.0, 60.0], [1, 1], [3, 3], [1, 1]),
    # preempted: left out of every reading
    (13, 6, 1, [500.0] * 5, [0] * 5, [9] * 5, [0] * 5)]
TAIL = (14, 2, 0, [77.0], [0], [4], [0])
# (t0, length ms, wait_ms, chunk, step); the judged window is [10, ~12]
PASSES = [(9.0, 50.0, 1.0, 0, 1),       # before the first judged submit
          (10.1, 10.0, 4.0, 0, 1),      # 6 ms of the host's own
          (10.2, 12.0, 5.0, 0, 1),      # 7 ms
          (10.3, 30.0, 3.0, 1, 1),      # carried a chunk
          (10.4, 5.0, 0.0, 0, 0),       # dispatched no step
          (20.0, 40.0, 2.0, 0, 1)]      # after the last judged end


def _buffer(lists=True, passes=True):
    spans, plan = [], {'judged': len(JUDGED), 'requests': []}
    rows = [WARM] + JUDGED + [TAIL]
    for rid, (n_prompt, max_new, pre, ms, chunks, lanes, sync) in \
            enumerate(rows):
        sub = 5.0 if rid == 0 else 10.0 + 0.05 * rid
        first = sub + 0.02
        done = first + sum(ms) / 1e3
        attrs = dict(n_prompt=n_prompt, max_new_tokens=max_new,
                     n_tokens=len(ms) + 1, state='DONE', preemptions=pre)
        dec = dict(attrs, gaps_ms=ms)
        if lists:
            dec.update(gap_chunks=chunks, gap_lanes=lanes, gap_sync=sync)
        for name, t0, t1, a in (('serve.queue', sub, sub + 0.01, attrs),
                                ('serve.prefill', sub + 0.01, first, attrs),
                                ('serve.decode', first, done, dec)):
            spans.append(dict(a, name=name, kind='request', sid=rid,
                              psid=None, t0=t0, t1=t1, tid=1))
        if 0 < rid <= len(JUDGED):
            plan['requests'].append({'prompt': [1] * n_prompt,
                                     'max_new': max_new, 'due': sub - 10.0})
    plan['requests'].append({'prompt': [1] * TAIL[0], 'max_new': TAIL[1],
                             'due': 9.0})
    for i, (t0, ms, wait, chunk, step) in enumerate(PASSES):
        attrs = dict(wait_ms=wait, chunk=chunk, step=step) if passes else {}
        spans.append(dict(attrs, name='serve.iter', kind='host',
                          sid=1000 + i, psid=None, t0=t0, t1=t0 + ms / 1e3,
                          tid=2, lanes=3, ready=3, prefilling=0, queued=0))
    return spans, plan


def _read_all(view, plan):
    man = manifest.load()
    run = {'plan': plan, '_gap_view': view}
    return [manifest.layer_metric(man, name).read(run) for name in SEVEN]


def test_the_view_splits_the_judged_gaps_by_kind():
    spans, plan = _buffer()
    v = gaps.view(spans, plan)
    assert v['n'] == 9 and v['requests'] == 3 and v['passes'] == 4
    assert {k: sorted(g[0] for g in v['gaps'][k]) for k in gaps.KINDS} == {
        'plain': [10.0, 10.0, 12.0], 'chunk': [30.0, 50.0],
        'sync': [20.0, 22.0, 40.0, 60.0]}
    assert [g[3] for g in v['gaps']['sync']] == [True, True, True, False]
    assert v['host_ms'] == pytest.approx([6.0, 7.0])
    assert v['window'] == pytest.approx((10.05, 12.72))
    # the identity the account rests on: shares times means, pooled
    pooled = sum(g[0] for k in gaps.KINDS for g in v['gaps'][k]) / v['n']
    parts = sum(len(v['gaps'][k]) / v['n']
                * sum(g[0] for g in v['gaps'][k]) / len(v['gaps'][k])
                for k in gaps.KINDS)
    assert parts == pytest.approx(pooled, rel=1e-12)


def test_the_seven_readers_to_the_digit():
    spans, plan = _buffer()
    plain, chunk, sync, chunk_share, sync_share, per_lane, host = \
        _read_all(gaps.view(spans, plan), plan)
    assert plain == 10.0                 # nearest rank of 10, 10, 12
    assert chunk == 30.0                 # the gap behind two chunks left out
    assert sync == 60.0                  # the requests' own first gaps left out
    assert chunk_share == pytest.approx(100.0 * 2 / 9)
    assert sync_share == pytest.approx(100.0 * 4 / 9)
    # lanes 1, 2, 3 against 10, 10, 12 ms: covariance 2 over variance 2
    assert per_lane == pytest.approx(1.0)
    assert host == pytest.approx(6.5)


def test_a_program_without_the_lists_gives_none_seven_times():
    for lists, passes in ((False, True), (True, False), (False, False)):
        spans, plan = _buffer(lists=lists, passes=passes)
        assert gaps.view(spans, plan) is None
        assert _read_all(gaps.view(spans, plan), plan) == [None] * 7
    spans, plan = _buffer()
    other = dict(plan, requests=[dict(r, max_new=r['max_new'] + 1)
                                 for r in plan['requests']])
    assert gaps.view(spans, other) is None       # not this process's plan
    assert gaps.view([], plan) is None


def test_lists_of_another_length_than_the_gaps_give_nothing():
    spans, plan = _buffer()
    for s in spans:
        if s['name'] == 'serve.decode' and s['sid'] == 2:
            s['gap_sync'] = s['gap_sync'][:-1]
    assert gaps.view(spans, plan) is None


def test_a_window_without_a_kind_reads_zero_not_nothing():
    """A rehearsal's four requests may hold no plain gap, no chunk gap,
    or no pass without a chunk: the readers say 0, which no window that
    holds one can read."""
    spans, plan = _buffer()
    for s in spans:
        if s['name'] == 'serve.decode':
            s['gap_sync'] = [1] * len(s['gap_sync'])
        elif s['name'] == 'serve.iter':
            s['chunk'] = 1
    plain, chunk, sync, chunk_share, sync_share, per_lane, host = \
        _read_all(gaps.view(spans, plan), plan)
    assert (plain, chunk, chunk_share, per_lane, host) == (0.0,) * 5
    # not a first gap: 10, 10, 12, 30, 50, 60
    assert sync_share == 100.0 and sync == 12.0
    # the same lanes in every plain gap: no slope to tell
    spans, plan = _buffer()
    for s in spans:
        if s['name'] == 'serve.decode':
            s['gap_lanes'] = [2] * len(s['gap_lanes'])
    assert _read_all(gaps.view(spans, plan), plan)[5] == 0.0


def test_the_view_is_made_once_a_run(monkeypatch):
    spans, plan = _buffer()
    calls = []
    monkeypatch.setattr(gaps.spans, 'program_spans',
                        lambda: calls.append(1) or spans)
    run = {'plan': plan}
    man = manifest.load()
    values = [manifest.layer_metric(man, name).read(run) for name in SEVEN]
    assert calls == [1] and None not in values
    assert run['_gap_view']['n'] == 9


def test_the_seven_entries_stand_at_the_end_and_agree_with_their_readers():
    """In order, together, behind the older ones (later PRs append behind
    them), the three cells first where they report."""
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    at = names.index(SEVEN[0])
    assert at > 0 and names[at:at + 7] == SEVEN
    units = dict(zip(SEVEN, ['ms', 'ms', 'ms', '%', '%', 'ms', 'ms']))
    for e in man['per_layer'][at:at + 7]:
        mod = manifest.layer_metric(man, e['name'])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == \
            (e['layer'], e['unit'], e['better'], e['source']), e['name']
        assert e['layer'] == 'engine (serving/engine.py)'
        assert (e['unit'], e['better'], e['source'], e['moves']) == \
            (units[e['name']], 'lower', 'program_span', 'tpot_p50_ms')
        assert e['workloads'][:len(CELLS)] == CELLS
        assert set(e) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
