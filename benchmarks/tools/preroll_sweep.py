"""How long a pre-roll a cell's window needs, once, on the chip: one
set-up, then for each seed a window after each length of pre-roll, the
same seeds under every length, the engine emptied between windows.

    python benchmarks/tools/preroll_sweep.py --workload olmohyb_serve_long \\
        --prerolls 0,5,10,20 --seeds 1,2,3,4,5,6 --seconds 45

Per length: tpot_p50_ms of each seed, their median, and their spread as
the driver takes it (quartiles of statistics.quantiles over the median).
The weights stay those of the first seed: a step's time does not know
them.
"""
import argparse
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--prerolls', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, default=45)
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
    traffic['params']['preroll_s'] = 0          # the sweep's own, below
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', runner.CACHE_DIR)
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    from paddle_tpu.obs import telemetry
    from paddle_tpu.serving import ServingEngine
    telemetry.enable()
    seeds = [int(s) for s in args.seeds.split(',')]
    prerolls = [float(p) for p in args.prerolls.split(',')]
    system = manifest.resolve(config['builder'])(
        config=config, traffic=traffic, devices=jax.devices()[:1],
        seed=seeds[0], phases=setup_clock.Phases(time.time()),
        rehearse=args.rehearse)
    system.warm_up(None)
    tracer = trace.Tracer(False, None, 0)
    got = {p: [] for p in prerolls}
    try:
        for seed in seeds:
            system.seed = seed
            plan = manifest.resolve(traffic['generator'])(
                traffic['params'], seed, config, args.seconds)
            for p in prerolls:
                if p > 0:
                    system.preroll(p)
                res = drives.open_loop(system, plan, args.seconds, tracer)
                c = res['counters']
                got[p].append(res['e2e']['tpot_p50_ms'])
                print('seed %d preroll %g: tpot_p50_ms %.4f failed %d '
                      'decode_batch_mean %.2f'
                      % (seed, p, got[p][-1], res['failed'],
                         c['decode_batch_sum']
                         / max(1, c['decode_batch_count'])), flush=True)
                # what is still in flight is cancelled, not waited for
                system.stop_engine()
                for slot in list(system.dec.slot_tokens()):
                    system.dec.release(slot)
                system.engine = ServingEngine(system.dec).start()
    finally:
        system.close()
    for p in prerolls:
        print('preroll %g: median %.4f spread %.4f  %s'
              % (p, statistics.median(got[p]), spread(got[p]),
                 ' '.join('%.3f' % v for v in got[p])), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
