"""Decode steps dispatched while the step before was still in flight (attr `overlapped`
of `paged.decode.tables`), as % of the decode steps made while the judged requests ran."""
LAYER = 'engine (serving/engine.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_span'


from harness import spans


def _judged_window(all_spans, plan):
    """(first judged submit, last judged end) on the spans' clock, the
    judged requests lined up as `spans.serving_view` lines them up;
    None where they cannot be."""
    queued = sorted((s for s in all_spans if s.get('kind') == 'request'
                     and s['name'] == 'serve.queue'), key=lambda s: s['t0'])
    want = [(len(r['prompt']), r['max_new'])
            for r in plan['requests'][:plan['judged']]]
    have = [(s.get('n_prompt'), s.get('max_new_tokens')) for s in queued]
    start = next((i for i in range(len(have) - len(want) + 1)
                  if have[i:i + len(want)] == want), None)
    if start is None or not want:
        return None
    sids = {s['sid'] for s in queued[start:start + len(want)]}
    ends = [s['t1'] for s in all_spans
            if s.get('kind') == 'request' and s['sid'] in sids
            and s['name'] != 'serve.requeue']
    return queued[start]['t0'], max(ends)


def read(run):
    """None on a program whose spans lack the attr (the parent of the PR
    that brought it): the metric is then left out of the line."""
    all_spans = spans.program_spans()
    if not all_spans or 'judged' not in run['plan']:
        return None
    window = _judged_window(all_spans, run['plan'])
    if window is None:
        return None
    iters = {s['sid'] for s in all_spans if s['name'] == 'serve.iter'
             and window[0] <= s['t0'] <= window[1]}
    steps = [s for s in all_spans if s['name'] == 'paged.decode.tables'
             and s.get('psid') in iters]
    if not steps or any('overlapped' not in s for s in steps):
        return None
    return 100.0 * sum(s['overlapped'] for s in steps) / len(steps)
