"""One run of a cell, then what the program's spans say about it:

    python benchmarks/tools/record_spans.py [--out FILE] [--slice-s 0.25] \\
        -- --workload <cell> --seed <n> --seconds <s> --trace 1

runs `harness.runner.main` with the arguments after `--` in this process
and afterwards prints, from the program's span buffer and from the
capture the runner left in `runner.TRACE_DIR`: how many spans of each
name there are and how many the buffer dropped, the serving or training
view's sample counts and means (`harness/spans.py`), the three longest
passes of the serving loop or training steps with their children (a run
that stalls shows where), and the device's idle gaps by the program span
the host was in. With `--out` it also
writes a short slice of the capture (device ops, `pt.*` and `bench.*`
host spans, cut at the edges of two program executions) in the format
of `runner --record-trace`: that is how
`tests/data/serve_chat_spans_v5e.json` was made, since `--record-trace`
itself keeps only the benchmark's own `bench.*` spans.
"""
import argparse
import collections
import io
import json
import os
import sys
import time

_WALL = time.time()
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(_HERE)))


class _Tee(io.TextIOBase):
    def __init__(self, out):
        self.out, self.lines = out, []

    def write(self, text):
        self.lines.append(text)
        return self.out.write(text)

    def flush(self):
        self.out.flush()


def _slice(events, seconds):
    """Events of the first device plane and the host's spans between the
    start of a program execution in the middle of the capture and the end
    of the last one that starts within `seconds` of it."""
    from harness import trace
    first = min(e[0] for e in events if e[0].startswith('/device:'))
    mods = sorted((e for e in events if e[0] == first
                   and e[1] == trace.MODULES_LINE), key=lambda e: e[3])
    start = mods[len(mods) // 2][3]
    end = max(e[3] + e[4] for e in mods
              if start <= e[3] <= start + int(seconds * 1e9))
    kept = []
    for e in events:
        host = not e[0].startswith('/device:')
        if host and not e[2].startswith(('pt.', 'bench.')):
            continue
        if not host and e[0] != first:
            continue
        if host and e[3] < end and e[3] + e[4] > start:
            kept.append(e)
        elif not host and start <= e[3] and e[3] + e[4] <= end:
            kept.append(e[:2] + (e[2][:24],) + e[3:])   # an op's kind is enough
    return {'window_s': (end - start) / 1e9, 'labels': {}, 'events': kept}


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--out', default=None)
    ap.add_argument('--slice-s', type=float, default=0.25)
    ap.add_argument('runner_args', nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    run_args = [a for a in args.runner_args if a != '--']
    from harness import runner, spans, trace
    tee = sys.stdout = _Tee(sys.stdout)
    try:
        rc = runner.main(run_args, _WALL)
    finally:
        sys.stdout = tee.out
    if rc:
        return rc
    out = ''.join(tee.lines).strip().splitlines()
    line = json.loads(out[-1])
    window = next((l for l in out if l.startswith('window ')), '')
    counters = dict(kv.split('=') for kv in window.split()[1:])
    got = spans.program_spans()
    from paddle_tpu.obs import telemetry
    print('spans %d dropped %d' % (
        len(got), telemetry.snapshot()['counters'].get('trace.dropped', 0)))
    print('span names ' + ' '.join('%s=%d' % kv for kv in sorted(
        collections.Counter(s['name'].split('(')[0] for s in got).items())))
    steps = int(float(counters.get('steps', 0)))
    tv = spans.training_view(got, steps) if steps else None
    if tv:
        runs = sorted(tv['run_ms'])
        print('training steps=%d run_ms p50=%.3f max=%.3f pop_ms max=%.3f '
              'waited_ms max=%.3f'
              % (len(runs), runs[len(runs) // 2], runs[-1],
                 max(tv['pop_ms'], default=0.0),
                 max((s.get('waited_ms', 0.0) for s in got
                      if s['name'] == 'host_op:read'), default=0.0)))
    reqs = collections.defaultdict(dict)
    for s in got:
        if s.get('kind') == 'request':
            reqs[s['sid']][s['name']] = s
    if reqs:
        # the readers' view needs the plan: here the decode call's split
        # over ALL iterations of the process, warm-up and tail included
        calls = spans.decode_calls(got)
        keys = ('prep', 'fetch', 'book', 'tables', 'run', 'feed', 'prepare',
                'dispatch')
        print('serving requests=%d decode_calls=%d mean ms: %s'
              % (len(reqs), len(calls), ' '.join(
                  '%s=%.4f' % (k, spans.mean([c[k] for c in calls]) or 0.0)
                  for k in keys)))
        iters = [s for s in got if s['name'] == 'serve.iter']
        print('serve.iter n=%d mean ms %.4f; children mean ms: %s' % (
            len(iters), spans.mean([spans._ms(s) for s in iters]),
            ' '.join('%s=%.4f' % (n, spans.mean(
                [spans._ms(s) for s in got if s['name'] == n]) or 0.0)
                for n in ('serve.admit', 'serve.prefill_tick', 'serve.pack',
                          'serve.accept', 'paged.prefill.tables',
                          'paged.prefill.book', 'paged.prefill.fetch'))))
        worst = 0.0
        for g in reqs.values():
            if 'serve.decode' in g:
                worst = max(worst, abs(
                    spans._ms(g['serve.queue']) + spans._ms(g['serve.prefill'])
                    - 1e3 * (g['serve.decode']['t0'] - g['serve.queue']['t0'])))
        print('queue+prefill vs first_token-submitted, worst |diff| ms %.9f'
              % worst)
    # a pass or a step that stalls (PERF.md section 6): the three longest
    # scopes of the loop, with what their time went to
    kids = spans._children(got)
    for top in ('serve.iter', 'exe.run'):
        longest = sorted((s for s in got if s['name'] == top
                          and (top != 'exe.run' or s['psid'] is None)),
                         key=spans._ms)[-3:]
        for s in reversed(longest):
            print('longest %s %.3f ms at +%.3f s: %s' % (
                top, spans._ms(s), s['t0'] - got[0]['t0'], ' '.join(
                    '%s=%.3f%s' % (k['name'].split('(')[0], spans._ms(k),
                                   '(waited %.3f)' % k['waited_ms']
                                   if 'waited_ms' in k else '')
                    for k in sorted(kids.get(s['sid'], ()),
                                    key=spans._ms)[-8:])))
    events = trace.read_xplane(runner.TRACE_DIR) \
        if '--trace' in run_args and os.path.isdir(runner.TRACE_DIR) else []
    if events and line.get('device', {}).get('window_s'):
        w = line['device']['window_s']
        split = spans.idle_split(events, w)
        if split:
            gaps = split.pop('gaps')
            print('idle split %% of window: ' + ' '.join(
                '%s=%.3f' % kv for kv in sorted(split.items()))
                + ' device_idle_share=%.3f' % (
                    100.0 * (1 - line['device']['busy_s'] / w)))
            print('idle gaps s: ' + ' '.join('%s=%.4f' % kv for kv in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:16]))
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, 'w') as f:
                json.dump(_slice(events, args.slice_s), f,
                          separators=(',', ':'))
            print('wrote %s (%d bytes)' % (args.out,
                                           os.path.getsize(args.out)))
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
