"""Whose the chip's idle time is, and where the worker's host time goes.

Two views of one run, both from spans the PROGRAM records
(`paddle_tpu.profiler.RecordEvent`: `serve.idle`, `serve.admit` with
`admitted`, `paged.open`, `paged.prefix.match` / `.register` / `.evict`),
for the ten `idle_*_share.tpot`, `engine_empty_share.tpot`,
`admit_ms_mean.tpot`, `prefix_match_ms_mean.tpot` and `evict_*.tpot`
readers under `layer_metrics/` and for `tools/host_account.py`:

- `partition(events, window_s)`, from the device trace: ONE partition of
  the idle gaps between the first chip's ops, as % of the traced window.
  A gap is first tested against the executions on the `XLA Modules`
  line: one that lies inside a single execution is the device's own
  (`in_program`: an awaited slice, a copy), whatever the host was doing,
  because the pipelined loop keeps the host a pass AHEAD of the device
  and the span over such a gap belongs to the next pass. Only a gap
  between two executions is named by the innermost `pt.` span over its
  middle: `empty` (`serve.idle`: the traffic's), `admit`, `cache`,
  `dispatch` (what `spans._class_of` calls feed), else `rest` (a fetch,
  `serve.pack`, `serve.accept`, no span). The six sum to `between_ops`,
  the idle time between ops that `spans.idle_split` sorts three ways;
  `device_idle_share` also counts the window's two edges.
- `host_view(all_spans, window)`, from the span buffer on
  `perf_counter()`, over the judged window of `gaps.view`: no device
  trace, so a `--trace 0` run gives it too, and there no profiler hooks
  the host's Python (a traced run's reading is nine tenths untraced
  seconds, the capture being the window's last few).

A program that records none of this (the parent of the PR that brought
the spans; known by a `serve.admit` span that does not say what it
`admitted`) gives None where a reading needs them: the reader returns
None and the metric is left out of the line. Any other program gives a
number everywhere, 0 where the window, or the capture, holds nothing of
the kind.
"""
from __future__ import annotations

import bisect
import collections

from . import gaps, spans, trace

__all__ = ['SHARES', 'partition', 'host_view', 'of_run', 'device_share',
           'host_value']

_EMPTY = ('serve.idle',)
_ADMIT = ('serve.admit', 'paged.open', 'paged.prefix.match')
_CACHE = ('paged.prefix.evict', 'paged.prefix.register')
SHARES = ('in_program', 'empty', 'admit', 'cache', 'dispatch')
# the shares that name a span only a marked program records
_MARKED = ('empty', 'admit', 'cache')


def _class_of(name):
    """The share a gap BETWEEN two executions goes to, by the program
    span (without its `pt.`) the host was in; None is no span."""
    if name in _EMPTY:
        return 'empty'
    if name in _ADMIT:
        return 'admit'
    if name in _CACHE:
        return 'cache'
    if name is not None \
            and spans._class_of('in:' + spans.PREFIX + name) == 'feed':
        return 'dispatch'
    return 'rest'


def _innermost(host, mids):
    """For each of the rising `mids`, the name of the span of `host`
    ((start, end, name), sorted) that covers it and started last: the
    innermost, where spans nest. None where none covers it."""
    out, stack, i = [], [], 0
    for mid in mids:
        while i < len(host) and host[i][0] <= mid:
            stack.append(host[i])
            i += 1
        while stack and stack[-1][1] < mid:
            stack.pop()
        out.append(stack[-1][2] if stack else None)
    return out


def partition(events, window_s):
    """{share: % of the traced window} for SHARES, 'rest' and their sum
    'between_ops', on the first chip: all 0 for a capture without two
    device ops (nothing lies between them); None without a window."""
    if not window_s:
        return None
    first = min((e[0] for e in events if e[0].startswith('/device:')),
                default=None)
    ops = [(e[3], e[3] + e[4]) for e in events
           if e[0] == first and e[1] == trace.OPS_LINE]
    host = sorted((e[3], e[3] + e[4], e[2][len(spans.PREFIX):])
                  for e in events if not e[0].startswith('/device:')
                  and e[2].startswith(spans.PREFIX))
    runs = sorted((e[3], e[3] + e[4]) for e in events
                  if e[0] == first and e[1] == trace.MODULES_LINE)
    starts = [s for s, _ in runs]
    busy = trace._union(ops)
    idle = [(e1, s2) for (_, e1), (s2, _) in zip(busy, busy[1:])]
    names = _innermost(host, [(s + e) // 2 for s, e in idle])
    out = dict.fromkeys(SHARES + ('rest',), 0.0)
    for (s, e), name in zip(idle, names):
        i = bisect.bisect_right(starts, s) - 1
        inside = i >= 0 and e <= runs[i][1]
        out['in_program' if inside else _class_of(name)] += \
            100.0 * (e - s) / 1e9 / window_s
    out['between_ops'] = sum(out.values())
    return out


def marked(all_spans):
    """Does the program record the spans this module reads? One whose
    `serve.admit` does not say what it admitted is the parent's."""
    return all('admitted' in s for s in all_spans
               if s['name'] == 'serve.admit')


def host_view(all_spans, window):
    """The worker's thread over `window` = (t0, t1) on the buffer's
    clock, seconds unless named otherwise; None for a program that is
    not `marked`.

      window_s, workers    the window's length; threads that ran passes
      idle_s               `serve.idle`, clipped to the window
      parts                {name: seconds} of the passes (`serve.iter`)
                           that began inside it: their direct children
                           by name (`device_segment:*` and the like cut
                           at the colon), 'pass' what a pass spent in
                           none of them; with 'idle' and 'between' (in
                           no pass and not idle) they sum to `window_s`
                           x `workers` but for the passes that straddle
                           its edges
      wait_s               blocked in a fetch (`wait_ms` of the passes)
      admit_s, admitted    the `serve.admit` spans that admitted, and
                           how many streams
      match_s, matches     `paged.prefix.match`
      register_s, registers    `paged.prefix.register`
      evict_s, evict_tick_s, evictions, scanned, freed
                           `paged.prefix.evict` (`_tick_s`: those under
                           a `serve.prefill_tick`), what they looked at
                           and how many gave a page's ref up
    """
    if not marked(all_spans):
        return None
    t0, t1 = window
    inside = [s for s in all_spans if t0 <= s['t0'] <= t1]
    passes = [s for s in inside if s['name'] == 'serve.iter']
    by_sid = {s['sid']: s for s in inside}
    parts = collections.defaultdict(float)
    for s in inside:
        parent = by_sid.get(s.get('psid'))
        if parent is not None and parent['name'] == 'serve.iter':
            parts[s['name'].split(':')[0]] += s['t1'] - s['t0']
    in_pass = sum(s['t1'] - s['t0'] for s in passes)
    parts['pass'] = in_pass - sum(parts.values())
    idle_s = sum(max(0.0, min(s['t1'], t1) - max(s['t0'], t0))
                 for s in all_spans if s['name'] == 'serve.idle')
    workers = len({s['tid'] for s in passes}) or 1
    parts['idle'] = idle_s
    parts['between'] = (t1 - t0) * workers - in_pass - idle_s

    def under(span, name):
        while span is not None and span['name'] != name:
            span = by_sid.get(span.get('psid'))
        return span is not None

    def named(name):
        return [s for s in inside if s['name'] == name]

    def seconds(group):
        return sum(s['t1'] - s['t0'] for s in group)
    admits = [s for s in named('serve.admit') if s.get('admitted')]
    matches = named('paged.prefix.match')
    registers = named('paged.prefix.register')
    evicts = named('paged.prefix.evict')
    return {
        'window_s': t1 - t0, 'workers': workers, 'idle_s': idle_s,
        'parts': dict(parts),
        'wait_s': sum(s.get('wait_ms', 0.0) for s in passes) / 1e3,
        'admit_s': seconds(admits),
        'admitted': sum(s['admitted'] for s in admits),
        'match_s': seconds(matches), 'matches': len(matches),
        'register_s': seconds(registers), 'registers': len(registers),
        'evict_s': seconds(evicts),
        'evict_tick_s': seconds([s for s in evicts
                                 if under(s, 'serve.prefill_tick')]),
        'evictions': len(evicts),
        'scanned': sum(s['scanned'] for s in evicts),
        'freed': sum(s['freed'] for s in evicts)}


def host_values(view):
    """The five span-buffer metrics of a `host_view`, by the name of
    their reader file less its `.tpot`."""
    return {
        'engine_empty_share': 100.0 * view['idle_s']
        / (view['window_s'] * view['workers']),
        'admit_ms_mean': 1e3 * view['admit_s'] / max(view['admitted'], 1),
        'prefix_match_ms_mean': 1e3 * view['match_s']
        / max(view['matches'], 1),
        'evict_ms_per_s': 1e3 * view['evict_s'] / view['window_s'],
        'evict_scan_per_page': view['scanned'] / max(view['freed'], 1)}


def of_run(run):
    """{'device': partition or None, 'host': host_view or None,
    'marked'} of one run, made once and kept on the run's dict, as
    `spans.of_run` and `gaps.of_run` keep theirs."""
    got = run.get('_idle_account')
    if got is None:
        all_spans = spans.program_spans()
        judged = gaps.of_run(run)
        got = run['_idle_account'] = {
            'marked': marked(all_spans), 'device': None,
            'host': host_view(all_spans, judged['window'])
            if judged else None}
        if run.get('trace'):
            from . import runner
            got['device'] = partition(trace.read_xplane(runner.TRACE_DIR),
                                      run['trace']['window_s'])
    return got


def device_share(run, share):
    """One share of the partition, in % of the traced window; None
    without one, or where the share names a span the program lacks."""
    got = of_run(run)
    if got['device'] is None or (share in _MARKED and not got['marked']):
        return None
    return got['device'][share]


def host_value(run, name):
    """One of `host_values`; None without a view."""
    view = of_run(run)['host']
    return None if view is None else host_values(view)[name]
