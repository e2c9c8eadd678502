"""tools/readings_hybrid.py for the GraniteMoeHybrid block: the two
readings a limit of `correct` is set from, in one process and one
set-up (the program's own comparisons over many seeds, then the
bf16-stored control's over the first few: reference/granite_h.py, prec
'bfloat16', on lanes of the check's own lengths, compared as the
program's logits are), and, with --wrong, what the invariant "never
pages without their state" protects, shown once: the same check with
every last turn's adoption left out, so that it runs over the right
pages and ANOTHER conversation's recurrent state (what its slot held),
and with the snapshot rows zeroed before they are adopted (pages with no
state at all). Both must miss the limits the program meets.

    python benchmarks/tools/readings_granite_h.py \\
        --workload granite4hs_serve_sessions --seeds 1,2,3,... \\
        [--controls 2] [--wrong]

A seed changes the weights as well as the inputs: each seed's tensors go
straight into the decoder's weight scope (a private attribute: a tool
may, a judged run never does).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from tools import readings_hybrid  # noqa: E402


def reseed(system, seed):
    from builders import granite_h as b
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
    from builders import gpt2, granite_h as b
    sv, serving = config['correct'], config['serving']
    sessions = b.check_sessions(seed, dims, sv, int(serving['page_tokens']))
    n = b.check_decoded(sessions, sv, int(serving['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    lanes = [list(last) + list(rng.integers(1, dims.vocab, size=k))
             for (_, last), k in zip(sessions, n)]
    got = [g for g, in b.serve_reference(seed, dims, lanes, n, 'bfloat16')]
    refs = b.serve_reference(seed, dims, lanes, n)
    return gpt2.serve_comparisons(got, [t for t, _ in refs],
                                  [s for _, s in refs], sv)


def wrong_state(system, how):
    """system.check() with the adoption spoiled: 'other' leaves it out
    (the slot keeps the state of the conversation that last ran there),
    'none' zeroes the row before it is copied."""
    dec = system.dec
    copy = dec._copy_state

    def spoiled(program, at, to):
        if program is not dec._pair.adopt_program:
            return copy(program, at, to)
        if how == 'none':
            for name in dec._pair.snapshot_names:
                rows = dec._scope.find_var(name)
                dec._scope.set_var(name, rows.at[at].set(0.0))
            return copy(program, at, to)

    dec._copy_state = spoiled
    try:
        return system.check()
    finally:
        del dec._copy_state


def main(argv):
    wrong = '--wrong' in argv
    argv = [a for a in argv if a != '--wrong']
    readings_hybrid.reseed, readings_hybrid.control = reseed, control
    if not wrong:
        return readings_hybrid.main(argv)
    # the loop's first seed again, after its readings, with the state
    # spoiled: the system is closed by then, so keep it open for these
    from harness import manifest
    built = {}
    resolve = manifest.resolve

    def keeping(spec):
        made = resolve(spec)
        if not spec.startswith('builders.'):
            return made

        def build(**kw):
            built['system'] = made(**kw)
            built['close'] = built['system'].close
            built['system'].close = lambda: None
            return built['system']
        return build

    manifest.resolve = keeping
    try:
        code = readings_hybrid.main(argv)
        system = built['system']
        for how in ('other', 'none'):
            checks = wrong_state(system, how)
            print('wrong state (%s) seed %d: %s' % (how, system.seed, ' '.join(
                '%s=%.6g%s' % (c['name'], c['value'],
                               '' if c['value'] <= c['limit'] else '(EXCEEDED)')
                for c in checks)), flush=True)
    finally:
        manifest.resolve = resolve
        if 'close' in built:
            built['close']()
    return code


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
