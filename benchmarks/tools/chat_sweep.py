"""The sweep that finds an open-loop mix's knee, once, on the chip: one
set-up, then a window at each rate, the engine drained between them.

    python benchmarks/tools/chat_sweep.py --workload gpt1b3_serve_chat \\
        --rates 1.5,2,2.5,3,3.5 --seconds 40 [--set serving.slots=32]

Per rate: the requests waiting for their first token at a third of the
window and at its end (from `Request` timestamps), ttft_p90_ms,
tpot_p50_ms, gen_late_p95_ms. The knee is the highest rate at which the
queue at the end is no longer than at the first third; the cell runs at
0.8 of it, written into the traffic file as a number.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def waiting_at(requests, t):
    return sum(1 for r in requests if r.submitted_at <= t and
               (r.first_token_at is None or r.first_token_at > t))


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--rates', required=True)
    ap.add_argument('--seconds', type=float, default=40)
    ap.add_argument('--seed', type=int, default=11)
    ap.add_argument('--set', action='append', default=[],
                    metavar='group.key=json', help='override a config key')
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    from harness import drives, manifest, runner, setup_clock, trace
    man = manifest.check(manifest.load())
    cell, cfg_entry = manifest.cell(man, args.workload)
    config = manifest.read_json(cfg_entry['file'])
    traffic = manifest.read_json(manifest.traffic_file(man, cell['traffic']))
    if args.rehearse:
        runner._env_for_rehearsal(1)
        config = runner._overlaid(config, config['rehearse'])
        traffic['params'].update(traffic['rehearse'])
    for item in args.set:
        key, _, val = item.partition('=')
        group, _, key = key.partition('.')
        config[group][key] = json.loads(val)
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', runner.CACHE_DIR)
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    from paddle_tpu.obs import telemetry
    telemetry.enable()
    phases = setup_clock.Phases(time.time())
    system = manifest.resolve(config['builder'])(
        config=config, traffic=traffic, devices=jax.devices()[:1],
        seed=args.seed, phases=phases, rehearse=args.rehearse)
    system.warm_up(None)
    print('serving %r; set-up %.1f s' % (config['serving'], phases.total()),
          flush=True)
    tracer = trace.Tracer(False, None, 0)
    try:
        for i, rate in enumerate(float(r) for r in args.rates.split(',')):
            params = dict(traffic['params'], rate_rps=rate)
            plan = manifest.resolve(traffic['generator'])(
                params, args.seed + i, config, args.seconds)
            res = drives.open_loop(system, plan, args.seconds, tracer)
            t0, reqs = res['t0'], res['requests']
            row = {'rate_rps': rate, 'judged': res['attempted'],
                   'failed': res['failed'],
                   'queue_at_third': waiting_at(reqs, t0 + args.seconds / 3),
                   'queue_at_end': waiting_at(reqs, t0 + args.seconds),
                   'gen_late_p95_ms': res['counters']['gen_late_p95_ms'],
                   'ttft_p90_ms': res['counters'].get('ttft_p90_ms'),
                   'decode_batch_mean': res['counters']['decode_batch_sum']
                   / max(1, res['counters']['decode_batch_count']),
                   'engine_step_ms': 1e3 * res['counters']['decode_s']
                   / max(1, res['counters']['decode_calls'])}
            row.update(res['e2e'])
            print(json.dumps(row), flush=True)
            system.engine.drain(180)
    finally:
        system.close()


if __name__ == '__main__':
    main(sys.argv[1:])
