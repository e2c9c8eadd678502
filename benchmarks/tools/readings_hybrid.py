"""tools/readings.py for the hybrid block: the two readings a limit of
`correct` is set from, in one process and one set-up. The program's own
comparisons over many seeds, then the bf16-stored control's over the
first few: the reference computed in bfloat16 storage
(reference/olmo_hybrid.py, prec 'bfloat16') on the check's own lanes,
compared as the program's logits are.

    python benchmarks/tools/readings_hybrid.py --workload olmohyb_serve_long \\
        --seeds 1,2,3,... [--controls 2]

A seed changes the weights as well as the inputs: each seed's tensors go
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
    from builders import olmo_hybrid as b
    system.seed = int(seed)
    spec = system.dec._pair.spec
    scope = system.dec._weight_scope
    for name in spec.param_names():
        scope.find_var(name).delete()
    b.put_seeded_weights(scope, spec, system.dims, seed)


def control(config, dims, seed):
    """The check's comparisons with the bf16-stored reference in the
    program's place, on lanes of the check's lengths."""
    import numpy as np
    from builders import gpt2, olmo_hybrid as b
    sv = config['correct']
    n = b.check_decoded(sv, int(config['serving']['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    lanes = [list(p) + list(rng.integers(1, dims.vocab, size=k))
             for p, k in zip(
                 gpt2.serve_probe(seed, dims, sv['prompt_tokens']), n)]
    got = [g for g, in b.serve_reference(seed, dims, lanes, n, 'bfloat16')]
    refs = b.serve_reference(seed, dims, lanes, n)
    return gpt2.serve_comparisons(got, [t for t, _ in refs],
                                  [s for _, s in refs], sv)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--controls', type=int, default=2)
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    from harness import manifest, runner, setup_clock
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
    worst, least = {}, {}
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
    for seed in seeds[:args.controls]:
        checks = control(config, system.dims, seed)
        print('control bfloat16 seed %d: %s' % (seed, ' '.join(
            '%s=%.6g' % (c['name'], c['value']) for c in checks)),
            flush=True)
        for c in checks:
            least[c['name']] = min(least.get(c['name'], 1e9), c['value'])
    print('control, smallest: %s' % ' '.join(
        '%s=%.6g' % kv for kv in sorted(least.items())), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
