"""The control of `correct`: the reference put in the program's place,
computed one precision below the one the configuration states (float8
for the bf16 training configuration, bfloat16 for the float32 serving
one), through the same comparison as a run's. It has to come out as NOT
correct; its smallest reading is the upper end for a limit.

    python benchmarks/tools/controls.py <config.json> <seed> [<seed> ...]

runs at the configuration's own size (on the chip) unless --rehearse.
tools/readings.py reads the program's own numbers and these in one
process.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def control_precision(config):
    return 'float8' if config['dtype'].startswith('bfloat16') else 'bfloat16'


def run_control(config, seed, prec=None, sequences=4):
    """The comparisons of one seed with the control in the program's
    place; each has 'value' and 'limit'. `sequences`: a training step's
    batch."""
    import numpy as np
    from builders import gpt2 as b
    from reference import gpt2 as ref
    prec = prec or control_precision(config)
    dims = ref.dims_of(config)
    limits = config['correct']
    if 'serving' not in config:
        seqs = b.train_probe(seed, dims, sequences)
        wanted = b.train_wanted(dims)
        want_loss, want = b.train_reference(seed, dims, seqs, wanted)
        got_loss, got = b.train_reference(seed, dims, seqs, wanted, prec)
        return b.train_comparisons(got_loss, got, want_loss, want, limits)
    n_decode = int(limits['decode_tokens'])
    lanes = [list(p) + [0] * n_decode for p in b.serve_probe(
        seed, dims, limits['prompt_tokens'])]
    # the tokens a stream decodes are whatever follows its prompt: here
    # seeded ones, since no program is there to choose them
    rng = np.random.default_rng([int(seed), 10])
    for lane in lanes:
        lane[-n_decode:] = rng.integers(1, dims.vocab, size=n_decode)
    refs = b.serve_reference(seed, dims, lanes, n_decode)
    got = [g for g, in b.serve_reference(seed, dims, lanes, n_decode, prec)]
    return b.serve_comparisons(got, [t for t, _ in refs],
                               [s for _, s in refs], limits)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('config')
    ap.add_argument('seeds', type=int, nargs='+')
    ap.add_argument('--sequences', type=int, default=4,
                    help="a training step's batch (the traffic's per_step)")
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    if args.rehearse:
        os.environ['JAX_PLATFORMS'] = 'cpu'
        from harness.runner import _overlaid
        config = _overlaid(config, config['rehearse'])
    failed_all = True
    for seed in args.seeds:
        checks = run_control(config, seed, sequences=args.sequences)
        passed = all(c['value'] <= c['limit'] for c in checks)
        failed_all &= not passed
        print('control %s seed %d: %s -> %s' % (
            control_precision(config), seed,
            ' '.join('%s=%.6g(limit %.6g)' % (c['name'], c['value'],
                                              c['limit']) for c in checks),
            'correct (THE COMPARISON DID NOT CATCH IT)' if passed
            else 'not correct'), flush=True)
    return 0 if failed_all else 1


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
