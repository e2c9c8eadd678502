"""Read recorded runs (run.py --trace 1 --record-run FILE) once more, by
another set of reader files beside the benchmark's own: a reader is host
code over a run's plain data, so two sets of readers are compared on the
SAME traced runs, with no second chip run.

    python3 benchmarks/tools/reread.py RUN.json [RUN.json ...]
        [--readers benchmarks/tests/data/readers_pr52]

prints, for every metric that has a reader file in --readers (default:
the 26 readers as they stood at PR 52, the parent of PR 53) and that the
run's cell lists, the other readers' value and the benchmark's; then, for
each cell with two recorded runs or more, how far the runs lie apart under
each set. The other set is handed the counters under the names it knew
(`parent_view`).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import manifest                                    # noqa: E402

PR52 = os.path.join(BENCH, 'tests', 'data', 'readers_pr52')


def load_run(path):
    """A recorded run as the readers are handed it: each request's
    prompt stands in by its length."""
    with open(path) as f:
        run = json.load(f)
    for r in run['plan']['requests']:
        r['prompt'] = range(r.pop('prompt_len'))
    return run


def parent_view(run):
    """The run under the counters' names of PR 52: the slice's keys of
    builders/axk1.py (`*_max`), builders/smallthinker.py
    (`slice_full_rows_read`), builders/solar_open2.py
    (`slice_chunk_tokens`) and builders/granite_h.py, whose
    `slice_live_tokens` was the least the pages read can hold."""
    c = dict(run['counters'])
    pt = int(run['config'].get('serving', {}).get('page_tokens', 16))
    lanes = c.get('slice_state_lanes', 0)
    if lanes:
        c['slice_live_tokens'] = \
            (c['slice_pages_read'] - lanes) * pt + lanes
    c['slice_latent_rows_max'] = c.get('slice_latent_rows', 0)
    c['slice_decode_calls_max'] = c.get('slice_decode_calls', 0)
    c['slice_full_rows_read'] = c.get('slice_rows_read', 0)
    c['slice_chunk_tokens'] = c.get('slice_state_tokens', 0)
    return dict(run, counters=c)


def reader(directory, name):
    path = os.path.join(directory, name + '.py')
    spec = importlib.util.spec_from_file_location(
        'reread_' + name.replace('.', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def both(run, man, directory):
    """{metric: (the other readers' value, the benchmark's)} for the
    cell's listed metrics that have a reader file in `directory`."""
    out, old = {}, parent_view(run)
    for m in manifest.metrics_of(man, 'per_layer', run['cell']['name']):
        if os.path.exists(os.path.join(directory, m['name'] + '.py')):
            out[m['name']] = (
                reader(directory, m['name']).read(old),
                manifest.layer_metric(man, m['name']).read(run))
    return out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('runs', nargs='+')
    ap.add_argument('--readers', default=PR52)
    args = ap.parse_args(argv)
    man = manifest.check(manifest.load(None))
    by_cell = {}
    for path in args.runs:
        run = load_run(path)
        got = both(run, man, args.readers)
        by_cell.setdefault(run['cell']['name'], []).append(got)
        print('%s  (%s)' % (run['cell']['name'], path))
        for name, (old, new) in got.items():
            print('  %-40s other %-12s benchmark %s'
                  % (name, _fmt(old), _fmt(new)))
    for cell, runs in by_cell.items():
        if len(runs) < 2:
            continue
        print('%s: widest distance between its %d runs' % (cell, len(runs)))
        for name in runs[0]:
            spans = [max(v) - min(v) if None not in v else None
                     for v in zip(*(r[name] for r in runs))]
            print('  %-40s other %-12s benchmark %s'
                  % (name, _fmt(spans[0]), _fmt(spans[1])))
    return 0


def _fmt(v):
    return 'None' if v is None else '%.4f' % v


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
