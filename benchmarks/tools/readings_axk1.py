"""tools/readings_hybrid.py for the A.X-K1 block: the two readings a
limit of `correct` is set from. The program's own comparisons are on
the `compared` lines of every judged run (each run another seed); this
tool adds the other reading, the bf16-stored control's: the reference
computed in bfloat16 storage (reference/axk1.py, prec 'bfloat16') on
lanes of the check's own lengths, compared as the program's logits are.

    python benchmarks/tools/readings_axk1.py --workload axk1_serve_docfollow \\
        --seeds 1,2,3 [--program]

With --program it is readings_hybrid's whole loop (one set-up, the
program's check over every seed, then the controls): a seed changes the
weights as well as the inputs, and each seed's tensors go straight into
the decoder's weight scope (a private attribute: a tool may, a judged
run never does). The corpus is not cached there: the check's fillers
prefill their documents cold, and later fillers open on them.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from tools import readings_hybrid  # noqa: E402


def reseed(system, seed):
    from builders import axk1 as b
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
    from builders import axk1 as b
    sv, serving = config['correct'], config['serving']
    prompts = b.check_prompts(seed, dims, sv, int(serving['page_tokens']))
    n = b.check_decoded(prompts, sv, int(serving['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    lanes = [list(p) + list(rng.integers(1, dims.vocab, size=k))
             for p, k in zip(prompts, n)]
    got = [g for g, in b.serve_reference(seed, dims, lanes, n, 'bfloat16')]
    refs = b.serve_reference(seed, dims, lanes, n)
    return b.comparisons(got, [t for t, _ in refs], [s for _, s in refs], sv)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--program', action='store_true')
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    readings_hybrid.reseed, readings_hybrid.control = reseed, control
    if args.program:
        n = len(args.seeds.split(','))
        return readings_hybrid.main(
            ['--workload', args.workload, '--seeds', args.seeds,
             '--controls', str(n)] + ['--rehearse'] * args.rehearse)
    from harness import manifest, runner
    from reference import axk1 as ref
    man = manifest.check(manifest.load())
    cell, cfg_entry = manifest.cell(man, args.workload)
    config = manifest.read_json(cfg_entry['file'])
    if args.rehearse:
        runner._env_for_rehearsal(cell['chips'])
        config = runner._overlaid(config, config['rehearse'])
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', runner.CACHE_DIR)
    least = {}
    for seed in (int(s) for s in args.seeds.split(',')):
        checks = control(config, ref.dims_of(config), seed)
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
