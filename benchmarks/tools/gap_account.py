"""One run of a cell, then the account of its tokens' gaps:

    python benchmarks/tools/gap_account.py -- --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

runs `harness.runner.main` with the arguments after `--` in this process
and afterwards prints one line `gap_account {...}` from the program's
span buffer (`harness/gaps.py`; no device trace needed, so `--trace 0`
runs give it too):

  kinds       per kind of gap (plain, chunk, sync): n, share %, mean and
              median ms; `chunk_one_p50` / `sync_later_p50` are the two
              medians the layer metrics report
  pooled_mean_ms, identity_rel   the mean of all judged gaps, and how far
              the sum over kinds of share x mean is from it
  adds_mean_ms   share x (the kind's mean - the plain mean): what the
              kind adds to the mean gap
  tpot_p50_ms    the median over requests of a request's mean gap over
              its plain gaps alone, over plain and chunk, over all (the
              last is the judged metric to the done_at's rounding); the
              differences are what each kind adds to `tpot_p50_ms`
  lanes_mean, ms_per_lane   over the gaps, and the plain gaps' slope
  passes      the passes of the judged window that dispatched a step and
              no chunk: n, mean length, mean wait_ms, mean host section
  loop        serving.loop.seconds / wait_seconds of the whole process
  sync_vs_overlapped   for every judged gap, the decode step that made
              its token (the last `paged.decode.tables` before the pass
              that accepted it) against `gap_sync`: how many were
              checked and how many disagree with 1 - `overlapped`
  programs    with --trace 1: ms a call of the decode and the prefill
              program on the device, in the traced slice
  slice       with --trace 1: the plain gaps and the step-and-no-chunk
              passes that ended inside the traced slice alone (the last
              seconds of the window, taken from the first judged submit:
              within a few ms of where the profile ran), to set beside
              `programs` on the same seconds
"""
import argparse
import bisect
import json
import os
import statistics
import sys
import time

_WALL = time.time()
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(_HERE)))

from tools.record_spans import _Tee      # noqa: E402


def _plan(run_args):
    """The plan the runner made from the same arguments."""
    from harness import manifest, runner
    args = runner._args(run_args)
    man = manifest.load(args.manifest)
    cell, cfg_entry = manifest.cell(man, args.workload)
    config = manifest.read_json(cfg_entry['file'])
    traffic = manifest.read_json(manifest.traffic_file(man, cell['traffic']))
    seconds = args.seconds if args.seconds is not None \
        else float(man['run_seconds'])
    if args.rehearse:
        config = runner._overlaid(config, config['rehearse'])
        traffic = dict(traffic, params=dict(traffic['params'],
                                            **traffic.get('rehearse', {})))
    return manifest.resolve(traffic['generator'])(
        traffic['params'], args.seed, config, seconds)


def _judged_gaps(all_spans, plan):
    """(accept time, ms, chunks, lanes, sync) of every gap of the judged
    requests that were not preempted, on the spans' clock."""
    from harness import gaps
    for g in gaps._judged(all_spans, plan) or ():
        dec = g.get('serve.decode')
        if dec is None or dec.get('preemptions'):
            continue
        t = dec['t0']
        for ms, chunks, lanes, sync in zip(dec['gaps_ms'], dec['gap_chunks'],
                                           dec['gap_lanes'], dec['gap_sync']):
            t += ms / 1e3
            yield t, ms, chunks, lanes, sync


def _p50_of_requests(gaps_, kinds):
    """Median over requests of a request's mean gap over `kinds`."""
    by_req = {}
    for k in kinds:
        for g in gaps_[k]:
            by_req.setdefault(g[4], []).append(g[0])
    means = [statistics.fmean(v) for v in by_req.values()]
    return statistics.median(means) if means else None


def _sync_vs_overlapped(all_spans, plan):
    """(gaps checked, gaps whose step's `overlapped` is not 1 - sync)."""
    passes = sorted((s for s in all_spans if s['name'] == 'serve.iter'),
                    key=lambda s: s['t0'])
    tables = sorted((s for s in all_spans
                     if s['name'] == 'paged.decode.tables'),
                    key=lambda s: s['t0'])
    pass_t0 = [s['t0'] for s in passes]
    table_t0 = [s['t0'] for s in tables]
    checked = wrong = 0
    for t, _ms, _chunks, _lanes, sync in _judged_gaps(all_spans, plan):
        i = bisect.bisect_right(pass_t0, t) - 1         # the accepting pass
        j = bisect.bisect_left(table_t0, pass_t0[i]) - 1 if i >= 0 else -1
        if j < 0:
            continue
        checked += 1
        wrong += tables[j]['overlapped'] != 1 - sync
    return checked, wrong


def _slice(all_spans, plan, t0, t1):
    """The plain gaps whose token was accepted in [t0, t1] and the
    passes with a step and no chunk that ended there."""
    from harness import spans
    plain = [(ms, lanes) for t, ms, chunks, lanes, sync
             in _judged_gaps(all_spans, plan)
             if t0 <= t <= t1 and not chunks and not sync]
    ms, lanes = [g[0] for g in plain], [g[1] for g in plain]
    held = [s for s in all_spans if s['name'] == 'serve.iter'
            and t0 <= s['t1'] <= t1 and s['step'] and not s['chunk']]
    return {'plain_n': len(ms), 'plain_mean': spans.mean(ms),
            'plain_p50': spans.percentile(ms, 0.5) if ms else None,
            'lanes_mean': spans.mean(lanes), 'passes': len(held),
            'pass_ms_mean': spans.mean([1e3 * (s['t1'] - s['t0'])
                                        for s in held]),
            'wait_ms_mean': spans.mean([s['wait_ms'] for s in held])}


def account(all_spans, plan):
    from harness import gaps, spans
    v = gaps.view(all_spans, plan)
    if v is None:
        return None
    out = {'n': v['n'], 'requests': v['requests'], 'kinds': {}}
    every = [g for k in gaps.KINDS for g in v['gaps'][k]]
    pooled = spans.mean([g[0] for g in every])
    parts = 0.0
    for k in gaps.KINDS:
        ms = [g[0] for g in v['gaps'][k]]
        mean = spans.mean(ms)
        out['kinds'][k] = {'n': len(ms), 'share': 100.0 * len(ms) / v['n'],
                           'mean': mean,
                           'p50': spans.percentile(ms, 0.5) if ms else None}
        parts += len(ms) / v['n'] * (mean or 0.0)
    one = [g[0] for g in v['gaps']['chunk'] if g[1] == 1]
    later = [g[0] for g in v['gaps']['sync'] if not g[3]]
    out['chunk_one_n'], out['sync_later_n'] = len(one), len(later)
    out['chunk_one_p50'] = spans.percentile(one, 0.5) if one else None
    out['sync_later_p50'] = spans.percentile(later, 0.5) if later else None
    out['sync_later_mean'] = spans.mean(later)
    out['pooled_mean_ms'] = pooled
    out['identity_rel'] = abs(parts - pooled) / pooled
    plain = out['kinds']['plain']['mean']
    out['adds_mean_ms'] = {
        k: out['kinds'][k]['share'] / 100.0 * (out['kinds'][k]['mean'] - plain)
        for k in ('chunk', 'sync') if out['kinds'][k]['n'] and plain}
    out['tpot_p50_ms'] = {
        'plain': _p50_of_requests(v['gaps'], ('plain',)),
        'plain+chunk': _p50_of_requests(v['gaps'], ('plain', 'chunk')),
        'all': _p50_of_requests(v['gaps'], gaps.KINDS)}
    out['lanes_mean'] = spans.mean([g[2] for g in every])
    out['lanes_mean_plain'] = spans.mean([g[2] for g in v['gaps']['plain']])
    out['ms_per_lane'] = gaps.slope({'_gap_view': v})
    out['passes'] = {
        'in_window': v['passes'], 'n': len(v['held']),
        'ms_mean': spans.mean([ms for ms, _ in v['held']]),
        'wait_ms_mean': spans.mean([wait for _, wait in v['held']]),
        'host_ms_mean': spans.mean(v['host_ms']),
        'host_ms_p50': spans.percentile(v['host_ms'], 0.5)
        if v['host_ms'] else None}
    out['sync_vs_overlapped'] = _sync_vs_overlapped(all_spans, plan)
    out['window_t0'] = v['window'][0]
    return out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('runner_args', nargs=argparse.REMAINDER)
    run_args = [a for a in ap.parse_args(argv).runner_args if a != '--']
    from harness import runner, spans
    kept = {}
    layer_metrics = runner._layer_metrics

    def keep(man, cell_name, run):
        kept.update(run)
        return layer_metrics(man, cell_name, run)
    runner._layer_metrics = keep
    tee = sys.stdout = _Tee(sys.stdout)
    try:
        rc = runner.main(run_args, _WALL)
    finally:
        sys.stdout = tee.out
        runner._layer_metrics = layer_metrics
    if rc:
        return rc
    lines = ''.join(tee.lines).strip().splitlines()
    line = json.loads(lines[-1])
    window = next((l for l in lines if l.startswith('window ')), '')
    counters = dict(kv.split('=') for kv in window.split()[1:])
    plan = _plan(run_args)
    out = account(spans.program_spans(), plan)
    if out is None:
        print('gap_account null')
        return 0
    args = runner._args(run_args)
    out['cell'], out['seed'] = args.workload, args.seed
    out['line'] = {k: m['value'] for k, m in line['metrics'].items()
                   if k.endswith('.tpot') or k == 'tpot_p50_ms'}
    if float(counters.get('decode_batch_count', 0)):
        out['decode_batch_mean'] = float(counters['decode_batch_sum']) \
            / float(counters['decode_batch_count'])
        out['decode_steps'] = float(counters['decode_batch_count'])
        out['prefill_calls'] = float(counters.get('prefill_calls', 0))
    from paddle_tpu.obs import telemetry
    snap = telemetry.snapshot()['counters']
    out['loop'] = {k: snap.get('serving.' + k) for k in (
        'loop.seconds', 'loop.wait_seconds', 'tokens_generated',
        'tokens_behind_prefill', 'tokens_behind_sync')}
    out['dropped'] = snap.get('trace.dropped', 0)
    if kept.get('trace'):
        out['programs'] = {
            name: {'calls': p['calls'],
                   'ms': 1e3 * p['device_s'] / p['calls']}
            for name, p in kept['trace']['programs'].items() if p['calls']}
        out['traced'] = {'busy_s': kept['trace']['busy_s'],
                         'window_s': kept['trace']['window_s']}
        seconds = args.seconds if args.seconds is not None else 45.0
        end = out['window_t0'] + seconds
        out['slice'] = _slice(spans.program_spans(), plan,
                              end - kept['trace']['window_s'], end)
    print('gap_account ' + json.dumps(out))
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
