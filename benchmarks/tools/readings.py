"""The two readings a limit of `correct` is set from, in one process and
one set-up: the program's own comparisons over many seeds, then the
control's (tools/controls.py) over the first few.

    python benchmarks/tools/readings.py --workload gpt1b3_serve_chat \\
        --seeds 1,2,3,... [--controls 3]

A seed changes the weights as well as the inputs. The training builder
re-seeds its own scope in check(); for serving, a rebuild through the
saved model costs 30 s a seed, so this tool puts each seed's weights
straight into the decoder's weight scope (a private attribute: a tool
may, a judged run never does).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def reseed(system, seed):
    from builders import gpt2 as b
    system.seed = int(seed)
    dec = getattr(system, 'dec', None)
    if dec is None:
        return                      # TrainSystem.check() re-seeds itself
    scope = dec._weight_scope
    names = [p.name for p in b._parameters(system.main)]
    for name in names:
        scope.find_var(name).delete()
    for name, value in b._seeded_weights(system.main, system.dims,
                                         seed).items():
        scope.set_var(name, value)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--controls', type=int, default=3)
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    from harness import manifest, runner, setup_clock
    from tools import controls
    seeds = [int(s) for s in args.seeds.split(',')]
    man = manifest.check(manifest.load())
    cell, cfg_entry = manifest.cell(man, args.workload)
    config = manifest.read_json(cfg_entry['file'])
    traffic = manifest.read_json(manifest.traffic_file(man, cell['traffic']))
    if args.rehearse:
        runner._env_for_rehearsal(cell['chips'])
        config = runner._overlaid(config, config['rehearse'])
        traffic['params'].update(traffic['rehearse'])
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', runner.CACHE_DIR)
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    system = manifest.resolve(config['builder'])(
        config=config, traffic=traffic,
        devices=jax.devices()[:cell['chips']], seed=seeds[0],
        phases=setup_clock.Phases(time.time()), rehearse=args.rehearse)
    worst = {}
    try:
        for i, seed in enumerate(seeds):
            if i:
                reseed(system, seed)
            t0 = time.perf_counter()
            checks = system.check()
            print('program seed %d: %s (%.1f s)' % (seed, ' '.join(
                '%s=%.6g' % (c['name'], c['value']) for c in checks),
                time.perf_counter() - t0), flush=True)
            for c in checks:
                worst[c['name']] = max(worst.get(c['name'], 0.0), c['value'])
    finally:
        system.close()
    print('program, largest over %d seeds: %s' % (len(seeds), ' '.join(
        '%s=%.6g' % kv for kv in sorted(worst.items()))), flush=True)
    least = {}
    for seed in seeds[:args.controls]:
        checks = controls.run_control(
            config, seed, sequences=traffic['params'].get('per_step', 4))
        print('control %s seed %d: %s' % (
            controls.control_precision(config), seed, ' '.join(
                '%s=%.6g' % (c['name'], c['value']) for c in checks)),
            flush=True)
        for c in checks:
            least[c['name']] = min(least.get(c['name'], 1e9), c['value'])
    print('control, smallest: %s' % ' '.join(
        '%s=%.6g' % kv for kv in sorted(least.items())), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
