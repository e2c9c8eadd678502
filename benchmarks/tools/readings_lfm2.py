"""tools/readings_hybrid.py for the LFM2 block: the two readings a limit
of `correct` is set from, in one process and one set-up (the program's
own comparisons over many seeds, then the bf16-stored control's over the
first few: reference/lfm2.py, prec 'bfloat16', on lanes of the check's
own lengths, compared as the program's logits are).

    python benchmarks/tools/readings_lfm2.py \\
        --workload lfm2_serve_agentloop --seeds 1,2,3,... [--controls 2]

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
    from builders import lfm2 as b
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
    from builders import gpt2, lfm2 as b
    sv, serving = config['correct'], config['serving']
    sessions = b.check_sessions(seed, dims, sv, int(serving['page_tokens']))
    n = b.check_decoded(sessions, sv, int(serving['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    lanes = [list(sessions[name]['last'])
             + list(rng.integers(1, dims.vocab, size=k))
             for name, k in zip(b.SESSIONS, n)]
    got = [g for g, in b.serve_reference(seed, dims, lanes, n, 'bfloat16')]
    refs = b.serve_reference(seed, dims, lanes, n)
    return gpt2.serve_comparisons(got, [t for t, _ in refs],
                                  [s for _, s in refs], sv)


def main(argv):
    readings_hybrid.reseed, readings_hybrid.control = reseed, control
    return readings_hybrid.main(argv)


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
