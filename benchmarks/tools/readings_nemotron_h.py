"""tools/readings_hybrid.py for the Nemotron-H block: the two readings a
limit of `correct` is set from, in one process and one set-up. The
program's own comparisons over many seeds, then the bf16-stored
control's over the first few: the reference computed in bfloat16 storage
(reference/nemotron_h.py, prec 'bfloat16') on the check's own lanes,
compared as the program's logits are.

    python benchmarks/tools/readings_nemotron_h.py \\
        --workload nemo3s_serve_reason --seeds 1,2,3,... [--controls 2]

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
    from builders import nemotron_h as b
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
    from builders import gpt2, nemotron_h as b, olmo_hybrid
    sv = config['correct']
    n = olmo_hybrid.check_decoded(sv, int(config['serving']['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    lanes = [list(p) + list(rng.integers(1, dims.vocab, size=k))
             for p, k in zip(
                 gpt2.serve_probe(seed, dims, sv['prompt_tokens']), n)]
    got = [g for g, in b.serve_reference(seed, dims, lanes, n, 'bfloat16')]
    refs = b.serve_reference(seed, dims, lanes, n)
    return gpt2.serve_comparisons(got, [t for t, _ in refs],
                                  [s for _, s in refs], sv)


if __name__ == '__main__':
    # readings_hybrid's loop over seeds, with this block's two hooks
    readings_hybrid.reseed, readings_hybrid.control = reseed, control
    sys.exit(readings_hybrid.main(sys.argv[1:]))
