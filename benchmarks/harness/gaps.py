"""A token's gap by what stood in front of its step: the judged requests'
gaps of the whole window, split by kind, and the host's own section of
the worker loop's passes.

The program marks every gap where it dispatches the step that ends it
(`paddle_tpu/serving/engine.py`, "What stood in front of a token"): the
`serve.decode` span of a request carries, beside `gaps_ms`, the lists
`gap_chunks` (prefill chunks dispatched in front of the step),
`gap_lanes` (lanes the step carried) and `gap_sync` (1: nothing was in
flight at the dispatch), and the kind of a gap is the program's own
`gap_kind`: `sync`, else `chunk`, else `plain`. A `serve.iter` span
carries `wait_ms` (blocked in a fetch), `chunk` and `step` (0/1: the pass
dispatched one). The seven `gap_*` / `*_gap_share` / `host_section_*`
readers under `layer_metrics/` all read the one view made here, over
all gaps of the judged requests (thousands a window), with no device
trace.

A program whose spans lack the lists (the parent of the PR that brought
them) gives no view: every reader then returns None and its metric is
left out of the line. Where the window holds nothing of what a reader
reads (a rehearsal of four requests in which every pass carries a
chunk; never 45 s on the chip) it reads 0, which no window that holds
one can read: no gap of the kind in a median or a share, no plain gap
or the same lanes in all of them in the slope, no pass with a step and
no chunk in the host's section.
"""
from __future__ import annotations

import collections

from . import spans

__all__ = ['view', 'of_run', 'KINDS', 'median', 'share', 'slope',
           'host_section']

KINDS = ('plain', 'chunk', 'sync')
_LISTS = ('gap_chunks', 'gap_lanes', 'gap_sync')


def _gap_kind():
    """The program's own definition of a gap's kind, None where it has
    none."""
    try:
        from paddle_tpu.serving.engine import gap_kind
        return gap_kind
    except ImportError:
        return None


def _judged(all_spans, plan):
    """The judged requests' spans by name, in submission order, lined up
    with the plan as `spans.serving_view` lines them up; None where they
    cannot be."""
    by_sid = collections.defaultdict(dict)
    for s in all_spans:
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
    return groups[start:start + len(want)]


def view(all_spans, plan):
    """The judged gaps by kind and the window's passes; None where the
    judged requests cannot be lined up or a `serve.decode` span lacks
    the lists. Requests that were preempted are left out.

      gaps        {kind: [(ms, chunks, lanes, first, request)]}: every
                  gap of the judged requests; `first` marks a request's
                  own first gap (the step behind its own last chunk),
                  `request` counts the requests the gaps come from
      n           how many gaps there are, all kinds together
      requests    how many requests they come from
      held        (length ms, wait_ms) of every pass of the judged
                  window that dispatched a decode step and no prefill
                  chunk; `host_ms` is the one less the other
      passes      how many passes the judged window holds
      window      (first judged submit, last judged end)
    """
    kind_of = _gap_kind()
    judged = _judged(all_spans, plan) if kind_of else None
    if not judged:
        return None
    gaps = {k: [] for k in KINDS}
    requests = 0
    for g in judged:
        dec = g.get('serve.decode')
        if dec is None:
            continue
        if any(name not in dec for name in _LISTS) or not \
                len(dec['gaps_ms']) == len(dec['gap_chunks']) \
                == len(dec['gap_lanes']) == len(dec['gap_sync']):
            return None
        if dec.get('preemptions'):
            continue
        requests += 1
        for i, (ms, chunks, lanes, sync) in enumerate(zip(
                dec['gaps_ms'], dec['gap_chunks'], dec['gap_lanes'],
                dec['gap_sync'])):
            gaps[kind_of(chunks, sync)].append(
                (ms, chunks, lanes, i == 0, requests))
    window = (judged[0]['serve.queue']['t0'],
              max(max(s['t1'] for s in g.values()) for g in judged))
    passes = [s for s in all_spans if s['name'] == 'serve.iter'
              and window[0] <= s['t0'] <= window[1]]
    if any('wait_ms' not in s for s in passes):
        return None
    held = [(1e3 * (s['t1'] - s['t0']), s['wait_ms'])
            for s in passes if s['step'] and not s['chunk']]
    return {'gaps': gaps, 'n': sum(len(v) for v in gaps.values()),
            'requests': requests, 'passes': len(passes), 'window': window,
            'held': held, 'host_ms': [ms - wait for ms, wait in held]}


def of_run(run):
    """The view of one run, made once and kept on the run's dict, as
    `spans.of_run` keeps its views; None where there is nothing to
    read."""
    if '_gap_view' not in run:
        all_spans = spans.program_spans()
        run['_gap_view'] = view(all_spans, run['plan']) \
            if all_spans and 'judged' in run['plan'] else None
    return run['_gap_view']


def median(run, kind, keep=lambda gap: True):
    """Median (nearest rank, as `tpot_p50_ms` is taken) of the gaps of
    `kind` that `keep` keeps; 0 where there is none; None without a
    view."""
    v = of_run(run)
    if v is None:
        return None
    ms = [g[0] for g in v['gaps'][kind] if keep(g)]
    return spans.percentile(ms, 0.50) if ms else 0.0


def share(run, kind):
    """Gaps of `kind` as % of all judged gaps; None without a view or
    without a gap."""
    v = of_run(run)
    if v is None or not v['n']:
        return None
    return 100.0 * len(v['gaps'][kind]) / v['n']


def slope(run, kind='plain'):
    """Least-squares slope of the gaps of `kind` on the lanes their
    steps carried, ms a lane; 0 where there is no such gap or all
    carried the same lanes; None without a view."""
    v = of_run(run)
    if v is None:
        return None
    ms = [g[0] for g in v['gaps'][kind]]
    lanes = [g[2] for g in v['gaps'][kind]]
    mean_ms, mean_lanes = spans.mean(ms), spans.mean(lanes)
    var = sum((n - mean_lanes) ** 2 for n in lanes)
    if not var:
        return 0.0
    return sum((n - mean_lanes) * (t - mean_ms)
               for n, t in zip(lanes, ms)) / var


def host_section(run):
    """Mean of the view's `host_ms`; 0 where no pass of the judged
    window dispatched a decode step without a chunk; None without a
    view."""
    v = of_run(run)
    if v is None:
        return None
    return spans.mean(v['host_ms']) or 0.0
