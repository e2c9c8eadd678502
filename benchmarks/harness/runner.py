"""One run of one cell: set-up by phase, the measured window, the
comparison with the reference, the result line."""
from __future__ import annotations

import argparse
import json
import os
import sys

from . import manifest, setup_clock, trace

CACHE_DIR = os.path.join(manifest.ROOT, '.jax_cache')
TRACE_DIR = os.path.join(manifest.ROOT, '.bench_trace')


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    ap.add_argument('--rehearse', action='store_true',
                    help='CPU walk-through at the tiny widths of the '
                         "configuration's `rehearse` group")
    ap.add_argument('--manifest', default=None, metavar='FILE',
                    help='another BENCHMARK.json (tests; its files are '
                         'still found from the root of the checkout)')
    ap.add_argument('--record-trace', metavar='FILE',
                    help='with --trace 1: also write the plain events and '
                         'their labels as JSON (how tests/data was made)')
    ap.add_argument('--record-run', metavar='FILE',
                    help='with --trace 1: also write what the readers are '
                         'handed (the plain data of the run, prompts as their '
                         'lengths) as JSON, for tools/reread.py')
    return ap.parse_args(argv)


def _overlaid(base, over):
    """base with over's keys; groups merge one level deep."""
    out = dict(base)
    for k, v in over.items():
        out[k] = dict(base.get(k, {}), **v) if isinstance(v, dict) else v
    return out


def _env_for_rehearsal(chips):
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '') +
                               ' --xla_force_host_platform_device_count=%d'
                               % chips)
    os.environ['FLAGS_pallas_interpret'] = '1'


def main(argv, wall_at_import):
    args = _args(argv)
    wall_start = setup_clock.process_start_wall(wall_at_import)
    man = manifest.check(manifest.load(args.manifest))
    cell, cfg_entry = manifest.cell(man, args.workload)
    config = manifest.read_json(cfg_entry['file'])
    traffic = manifest.read_json(manifest.traffic_file(man, cell['traffic']))
    seconds = args.seconds if args.seconds is not None \
        else float(man['run_seconds'])
    chips = int(cell['chips'])
    if args.rehearse:
        _env_for_rehearsal(chips)
        config = _overlaid(config, config['rehearse'])
        traffic = dict(traffic, params=dict(traffic['params'],
                                            **traffic.get('rehearse', {})))
    # the compile cache: where the environment says, else a fixed path in
    # the checkout (the program reads the same variable and sets nothing
    # else). No floor on compile time: a warm run compiles nothing.
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', CACHE_DIR)
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    misses = setup_clock.CompileMisses()
    devices = jax.devices()
    phases = setup_clock.Phases(wall_start)
    phases.mark('init')
    want = 'cpu' if args.rehearse else 'tpu'
    if devices[0].platform != want or len(devices) < chips:
        sys.stderr.write('cell %s needs %d %s device(s); JAX reports %d x %s\n'
                         % (cell['name'], chips, want, len(devices),
                            devices[0].platform))
        return 3
    devices = devices[:chips]

    from paddle_tpu.obs import telemetry
    telemetry.enable()                  # the registry only, no exporter
    tracer = trace.Tracer(args.trace, TRACE_DIR,
                          traffic['params'].get('trace_seconds', 4))
    system = manifest.resolve(config['builder'])(
        config=config, traffic=traffic, devices=devices, seed=args.seed,
        phases=phases, rehearse=args.rehearse)
    try:
        plan = manifest.resolve(traffic['generator'])(
            traffic['params'], args.seed, config, seconds)
        system.warm_up(plan)            # marks 'load' and 'warm'
        tracer.warm()
        phases.skip()
        setup_misses, setup_asked = misses.misses, misses.asked
        phases.detail += sorted(misses.seconds.items())
        result = manifest.resolve(traffic['drive'])(
            system, plan, seconds, tracer)
        setup_s = phases.total()
        # any backend compile asked for inside the window, served by the
        # cache or not (the executors' own count is compiled_segments)
        result['counters']['xla_compile_requests'] = \
            misses.asked - setup_asked
        # the peak of the system under test, before the reference runs
        peak = max((d.memory_stats() or {}).get('peak_bytes_in_use', 0)
                   for d in devices)
        hlo_texts = system.hlo_texts() if args.trace else []
        checks = system.check()
    finally:
        system.close()

    correct = bool(checks) and all(c['value'] <= c['limit'] for c in checks) \
        and result['failed'] == 0
    print('setup phases ' + ' '.join(
        '%s=%.3f' % (p, phases.seconds[p]) for p in setup_clock.PHASES)
        + ' total=%.3f compile_misses=%d' % (setup_s, setup_misses))
    print('setup detail ' + ' '.join('%s=%.3f' % d for d in phases.detail))
    print('window ' + ' '.join('%s=%.6g' % kv for kv in
                               sorted(result['counters'].items())
                               if isinstance(kv[1], (int, float))))

    device = {'platform': devices[0].platform,
              'kind': devices[0].device_kind, 'count': chips,
              'memory_peak_bytes': int(peak)}
    e2e = dict(result['e2e'], setup_s=setup_s)
    line = {'correct': correct, 'attempted': result['attempted'],
            'failed': result['failed'], 'device': device}
    if args.trace:
        events = tracer.events()
        labels = trace.labels_from_hlo(events, hlo_texts) if events else {}
        red = trace.reduce_events(
            events, tracer.window_s, labels,
            config.get('trace_programs')) if events else None
        if args.record_trace and events:
            kept = [e[:2] + (e[2][:96],) + e[3:] for e in events
                    if e[0].startswith('/device:')
                    or e[2].startswith('bench.')]
            with open(args.record_trace, 'w') as f:
                json.dump({'window_s': tracer.window_s, 'labels': labels,
                           'events': kept}, f)
        if red is not None and red['chips']:
            device['busy_s'] = red['busy_s']
            device['window_s'] = red['window_s']
            line['breakdown'] = trace.breakdown(red)
        counters = dict(result['counters'],
                        setup_compile_misses=setup_misses)
        # what a layer metric's reader is handed: plain data of one run
        run = dict(cell=cell, config=config, traffic=traffic, plan=plan,
                  e2e=e2e, counters=counters, setup=dict(phases.seconds),
                  trace=red, device=device, chips=chips)
        if args.record_run:
            _record_run(args.record_run, run)
        line['metrics'] = _layer_metrics(man, cell['name'], run)
    else:
        units = {m['name']: m['unit'] for m in man['end_to_end']}
        line['metrics'] = {
            m['name']: {'value': e2e[m['name']], 'unit': units[m['name']]}
            for m in manifest.metrics_of(man, 'end_to_end', cell['name'])
            if m['name'] in e2e}
    lacks = manifest.lacking(man, 'per_layer' if args.trace else 'end_to_end',
                             cell['name'], line['metrics'])
    if lacks:
        sys.stderr.write('the result line lacks %s, which BENCHMARK.json '
                         'lists for this cell\n' % ', '.join(lacks))
    if args.rehearse:
        # a CPU timing is never written under a metric's name: counts only
        counts = {m['name'] for m in man['per_layer']
                  if m['source'] == 'program_counter'}
        line['metrics'] = {k: v for k, v in line['metrics'].items()
                           if k in counts}
        line['rehearsal'] = True
    # each number compared beside its limit: the last lines of standard
    # error, and the result line's last key (what a refusal keeps of a run)
    line['compared'] = {c['name']: {'value': float(c['value']),
                                    'limit': float(c['limit'])}
                        for c in checks}
    line['compared']['failed'] = {
        'value': float(result['failed']), 'limit': 0.0}
    sys.stdout.flush()
    for c in checks:
        sys.stderr.write('compared %-28s %.6g (limit %.6g)%s\n'
                         % (c['name'], c['value'], c['limit'],
                            '' if c['value'] <= c['limit'] else '  EXCEEDED'))
    sys.stderr.write('compared %-28s %d (limit 0)\n'
                     % ('failed', result['failed']))
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def _record_run(path, run):
    """The run's dict as JSON; of the plan only what a reader takes: the
    judged count and each request's prompt length, output and due time."""
    plan = run['plan']
    kept = dict(run, plan={
        'judged': plan.get('judged'),
        'requests': [{'prompt_len': len(r['prompt']),
                      'max_new': int(r['max_new']), 'due': float(r['due'])}
                     for r in plan.get('requests', ())]})
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, 'w') as f:
        json.dump(kept, f, default=float)


def _layer_metrics(man, cell_name, run):
    """Each per-layer metric of the cell through its own reader. A
    reader that finds nothing to read returns None and the metric is
    left out of the line; a metric taken from the device's trace is
    never reported off a TPU."""
    out = {}
    on_tpu = run['device']['platform'] == 'tpu'
    for m in manifest.metrics_of(man, 'per_layer', cell_name):
        if m['source'] == 'device_trace' and not (on_tpu and run['trace']):
            continue
        value = manifest.layer_metric(man, m['name']).read(run)
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out
