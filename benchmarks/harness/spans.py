"""What the per-layer metrics read of the PROGRAM's own spans.

The program times its own work with one scoped span
(`paddle_tpu.profiler.RecordEvent`), which has two sinks. Both are
read here, after the window:

- `paddle_tpu.obs.trace.spans()`: every span of the process on
  `time.perf_counter()`, the clock of the `Request` timestamps and of
  the drives' arithmetic. `serving_view` picks the judged requests'
  spans and the worker loop's passes out of it, `training_view` the
  window's steps.
- the profiler's capture: while a trace runs, every span is also a
  `pt.<name>` TraceMe event in the xplane's host plane, on the clock of
  the device's ops. `idle_split` names each idle gap of the device by
  the innermost program span that covers its middle (the reduction is
  `harness/trace.reduce_events`, with the `pt.` prefix in place of the
  benchmark's own `bench.`), and sorts the gaps into three shares.

A program that has no such buffer or no such spans (the parent of the PR
that brought them) gives empty views: every reader then returns None
and its metric is left out of the line.
"""
from __future__ import annotations

import collections
import statistics

from . import trace
from .drives import _percentile as percentile

__all__ = ['program_spans', 'serving_view', 'decode_calls', 'training_view',
           'idle_split', 'of_run', 'percentile', 'mean']

PREFIX = 'pt.'
# the host is preparing or dispatching the next program / is waiting for
# a result; anything else (scheduling, bookkeeping, no span) is elsewhere
_FEED = ('exe.feed', 'exe.prepare', 'exe.run')


def program_spans():
    """The program's span buffer as a list of dicts, [] where the
    program has none."""
    try:
        from paddle_tpu.obs import trace as program_trace
        return program_trace.spans()
    except (ImportError, AttributeError):
        return []


def _ms(span):
    return 1e3 * (span['t1'] - span['t0'])


def mean(values):
    return statistics.fmean(values) if values else None


def _children(spans):
    kids = collections.defaultdict(list)
    for s in spans:
        if s.get('psid') is not None:
            kids[s['psid']].append(s)
    for group in kids.values():
        group.sort(key=lambda s: s['t0'])
    return kids


def serving_view(spans, plan):
    """The judged requests and the worker loop while they ran.

    Requests are the spans of kind 'request', grouped by sid (the
    request's id) and put in submission order; the judged are the
    `plan['judged']` consecutive ones whose prompt and output lengths
    are the plan's (the warm-up's requests come before them, the tail's
    after). Returns None if they cannot be lined up, else

      queue_ms, prefill_ms   one value per judged request that reached
                             the phase (`serve.queue`, `serve.prefill`)
      ttft_ms                first_token_at - submitted_at of the same
      gaps_ms                every inter-token gap of the judged
      decode_calls           decode_calls() between the first judged
                             submit and the last judged end
      judged                 how many requests were lined up
    """
    by_sid = collections.defaultdict(dict)
    for s in spans:
        if s.get('kind') == 'request' and s['name'] != 'serve.requeue':
            by_sid[s['sid']][s['name']] = s
    groups = sorted((g for g in by_sid.values() if 'serve.queue' in g),
                    key=lambda g: g['serve.queue']['t0'])
    want = [(len(r['prompt']), r['max_new'])
            for r in plan['requests'][:plan['judged']]]
    have = [(g['serve.queue'].get('n_prompt'),
             g['serve.queue'].get('max_new_tokens')) for g in groups]
    start = next((i for i in range(len(have) - len(want) + 1)
                  if have[i:i + len(want)] == want), None)
    if start is None or not want:
        return None
    judged = groups[start:start + len(want)]
    view = {'judged': len(judged), 'queue_ms': [], 'prefill_ms': [],
            'ttft_ms': [], 'gaps_ms': []}
    for g in judged:
        view['queue_ms'].append(_ms(g['serve.queue']))
        if 'serve.decode' in g:
            view['prefill_ms'].append(_ms(g['serve.prefill']))
            view['ttft_ms'].append(1e3 * (g['serve.decode']['t0']
                                          - g['serve.queue']['t0']))
            view['gaps_ms'].extend(g['serve.decode'].get('gaps_ms', ()))
    t_first = judged[0]['serve.queue']['t0']
    t_last = max(max(s['t1'] for s in g.values()) for g in judged)
    view['decode_calls'] = decode_calls(spans, t_first, t_last)
    return view


def decode_calls(spans, t_first=float('-inf'), t_last=float('inf')):
    """One dict of milliseconds per decode step made in a `serve.iter`
    that started in [t_first, t_last]: 'prep' (`paged.decode.tables` +
    the `exe.run` that follows it), 'fetch', 'book', and prep's parts
    'tables', 'run', 'feed', 'prepare', 'dispatch' (`exe.run`'s
    children; the rest of `run` is its own argument gather)."""
    kids = _children(spans)
    parts = {'exe.feed': 'feed', 'exe.prepare': 'prepare'}
    out = []
    for it in spans:
        if it['name'] != 'serve.iter' or not t_first <= it['t0'] <= t_last:
            continue
        call = None
        for s in kids.get(it['sid'], ()):
            if s['name'] == 'paged.decode.tables':
                call = dict.fromkeys(('run', 'fetch', 'book', 'feed',
                                      'prepare', 'dispatch'), 0.0)
                call['tables'] = call['prep'] = _ms(s)
                out.append(call)
            elif call is None:
                continue
            elif s['name'] == 'exe.run' and not call['run']:
                call['run'] = _ms(s)
                call['prep'] += _ms(s)
                for k in kids.get(s['sid'], ()):
                    part = parts.get(k['name'], 'dispatch' if k['name']
                                     .startswith('device_segment:') else None)
                    if part:
                        call[part] += _ms(k)
            elif s['name'] == 'paged.decode.fetch':
                call['fetch'] += _ms(s)
            elif s['name'] == 'paged.decode.book':
                call['book'] += _ms(s)
    return out


def training_view(spans, steps):
    """The window's steps: the last `steps` `exe.run` spans of the
    fingerprint run most often (the warm-up's steps come before them;
    the comparison's step after the window fetches other variables, so
    it is another prepared program). Returns None if there are fewer,
    else {'run_ms': [...], 'pop_ms': [...]} with `host_op:read` of the
    same steps."""
    runs = [s for s in spans if s['name'] == 'exe.run'
            and s.get('fingerprint')]
    if not runs or not steps:
        return None
    most = collections.Counter(s['fingerprint'] for s in runs) \
        .most_common(1)[0][0]
    mine = [s for s in runs if s['fingerprint'] == most][-int(steps):]
    if len(mine) < steps:
        return None
    kids = _children(spans)
    return {'run_ms': [_ms(s) for s in mine],
            'pop_ms': [_ms(k) for s in mine for k in kids.get(s['sid'], ())
                       if k['name'] == 'host_op:read']}


def _class_of(gap_name):
    if not gap_name.startswith('in:' + PREFIX):
        return 'elsewhere'
    name = gap_name[len('in:' + PREFIX):]
    if name in _FEED or name.endswith('.tables') \
            or name.startswith('device_segment:'):
        return 'feed'
    if name.endswith('.fetch'):
        return 'fetch'
    return 'elsewhere'


def idle_split(events, window_s):
    """The device's idle gaps by what the host was doing, as % of the
    traced window: 'feed' (in a `*.tables`, `exe.feed`, `exe.prepare`
    span, the rest of `exe.run`, or a dispatch), 'fetch' (in a
    `*.fetch` span), 'elsewhere' (any other program span, or none),
    and 'no_span', the part of 'elsewhere' that no program span covers.
    They sum to the idle time BETWEEN device ops; `device_idle_share`
    also counts the window's two edges. None without device events or
    without any program span in the capture."""
    red = trace.reduce_events(events, window_s, span_prefix=PREFIX)
    if not red['chips'] or not red['span_calls'] or not window_s:
        return None
    out = {'feed': 0.0, 'fetch': 0.0, 'elsewhere': 0.0, 'no_span': 0.0}
    for name, seconds in red['gaps'].items():
        out[_class_of(name)] += 100.0 * seconds / window_s
        if not name.startswith('in:'):
            out['no_span'] += 100.0 * seconds / window_s
    out['gaps'] = red['gaps']
    return out


def of_run(run):
    """The views of one run, made once and kept on the run's dict (every
    reader of a line is handed the same one): {'serving', 'training',
    'idle'}, each None where there is nothing to read."""
    views = run.get('_program_spans')
    if views is None:
        spans = program_spans()
        plan, counters = run['plan'], run['counters']
        views = run['_program_spans'] = {
            'serving': serving_view(spans, plan)
            if spans and 'judged' in plan else None,
            'training': training_view(spans, counters.get('steps'))
            if spans else None,
            'idle': None}
        if run.get('trace'):
            from . import runner
            views['idle'] = idle_split(trace.read_xplane(runner.TRACE_DIR),
                                       run['trace']['window_s'])
    return views
