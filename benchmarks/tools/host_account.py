"""One run of a cell, then the account of its worker's host time:

    python benchmarks/tools/host_account.py -- --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

runs `harness.runner.main` with the arguments after `--` in this process
(as `gap_account.py` does) and afterwards prints one line
`host_account {...}` from the program's span buffer, through the
functions of `harness/idle_account.py` that the layer metrics read. No
device trace is needed, so a `--trace 0` run gives it too: that is the
clean reading of the five span-buffer metrics, with no profiler hooking
the host's Python.

  window_s, workers   the judged window (first judged submit to the last
              judged end) and the threads that ran passes in it
  split       {part: [seconds, % of the window]}: `serve.idle`; of the
              passes, their direct children by name (`serve.admit`,
              `serve.prefill_tick`, `serve.pack`, the decode call's
              `paged.decode.tables` / `exe.run` / `paged.decode.book` /
              `paged.decode.fetch`, `serve.accept`, ...), `pass` (in a
              pass and in none of them) and `between` (in no pass and
              not idle); `sum_s` is their sum, to set against the window
  of_which    seconds inside the parts above: `wait` (blocked in a
              fetch, `wait_ms` of the passes), `paged.prefix.match`
              (under `serve.admit`), `paged.prefix.evict` (all, and
              those under `serve.prefill_tick`), `paged.prefix.register`
  counts      streams admitted, matches, registrations, evictions, the
              entries they scanned and the refs they freed
  metrics     the five span-buffer metrics, as their readers compute them
  device      with --trace 1: the partition of the idle time between ops
              (`idle_*_share.tpot`, `rest`, `between_ops`)
  loop, dropped   serving.loop.* and serving.prefix.* of the whole
              process; spans that fell out of the buffer
"""
import argparse
import json
import os
import sys
import time

_WALL = time.time()
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(_HERE)))

from tools.gap_account import _plan      # noqa: E402


def account(view):
    """The printed form of an `idle_account.host_view`."""
    from harness import idle_account
    total = view['window_s'] * view['workers']
    split = {name: [s, 100.0 * s / total]
             for name, s in sorted(view['parts'].items(),
                                   key=lambda kv: -kv[1])}
    return {
        'window_s': view['window_s'], 'workers': view['workers'],
        'split': split, 'sum_s': sum(view['parts'].values()),
        'of_which': {'wait': view['wait_s'],
                     'paged.prefix.match': view['match_s'],
                     'paged.prefix.evict': view['evict_s'],
                     'paged.prefix.evict@prefill_tick': view['evict_tick_s'],
                     'paged.prefix.register': view['register_s']},
        'counts': {k: view[k] for k in ('admitted', 'matches', 'registers',
                                        'evictions', 'scanned', 'freed')},
        'metrics': idle_account.host_values(view)}


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('runner_args', nargs=argparse.REMAINDER)
    run_args = [a for a in ap.parse_args(argv).runner_args if a != '--']
    from harness import idle_account, runner
    kept = {}
    layer_metrics = runner._layer_metrics

    def keep(man, cell_name, run):
        # a traced run's readers have made the account: keep their run
        line = layer_metrics(man, cell_name, run)
        kept.update(run)
        return line
    runner._layer_metrics = keep
    try:
        rc = runner.main(run_args, _WALL)
    finally:
        runner._layer_metrics = layer_metrics
    if rc:
        return rc
    args = runner._args(run_args)
    run = kept or {'plan': _plan(run_args)}
    got = idle_account.of_run(run)
    if got['host'] is None:
        print('host_account null')
        return 0
    out = account(got['host'])
    out['cell'], out['seed'], out['traced'] = \
        args.workload, args.seed, args.trace
    if got['device'] is not None:
        out['device'] = got['device']
    from paddle_tpu.obs import telemetry
    snap = telemetry.snapshot()['counters']
    out['loop'] = {k: snap.get('serving.' + k) for k in (
        'loop.seconds', 'loop.wait_seconds', 'loop.idle_seconds',
        'prefix.evictions', 'prefix.entries_scanned',
        'prefix.evict_seconds')}
    out['dropped'] = snap.get('trace.dropped', 0)
    print('host_account ' + json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
