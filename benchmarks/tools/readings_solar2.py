"""tools/readings_granite_h.py for the Solar-Open2 block: the two
readings a limit of `correct` is set from, in one process and one
set-up (the program's own comparisons over many seeds, then the
bf16-stored control's over the first few: reference/solar_open2.py,
prec 'bfloat16', on lanes of the check's own lengths, compared as the
program's logits are), and, with --wrong, the same check with every
compared stream's adoption left out (the right pages over ANOTHER
prompt's delta state: what its slot held) and with the snapshot rows
zeroed before they are adopted. Both must miss the limits the program
meets.

    python benchmarks/tools/readings_solar2.py \\
        --workload solar2_serve_chat_shared --seeds 1,2,3,... \\
        [--controls 2] [--wrong] [--rehearse] [--rows FILE]

A seed changes the weights as well as the inputs: each seed's tensors go
straight into the decoder's weight scope (a private attribute: a tool
may, a judged run never does).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from tools import readings_granite_h  # noqa: E402


def reseed(system, seed):
    from builders import solar_open2 as b
    system.seed = int(seed)
    spec = system.dec._pair.spec
    scope = system.dec._weight_scope
    for name in spec.param_names():
        scope.find_var(name).delete()
    b.put_seeded_weights(scope, spec, system.dims, seed)


def control_lanes(config, traffic, dims, seed):
    """The check's compared streams with seeded tokens where the
    program would have decoded: (lanes, tokens decoded a lane)."""
    import numpy as np
    from builders import solar_open2 as b
    from harness import traffic_sessions
    sv = config['correct']
    system = traffic_sessions.system_prompts(traffic['params'], config)
    n = b.check_decoded(sv, int(config['serving']['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    return [list(prompt) + list(rng.integers(1, dims.vocab, size=k))
            for (_, prompt), k in zip(
                b.check_streams(seed, dims, sv, system), n)], n


def control(config, dims, seed, traffic=None):
    """The check's comparisons with the bf16-stored reference in the
    program's place, on lanes of the check's lengths."""
    from builders import solar_open2 as b
    from harness import manifest
    traffic = traffic or manifest.read_json(
        'benchmarks/traffic/chat_shared_sys_open.json')
    lanes, n = control_lanes(config, traffic, dims, seed)
    got = [g for g, in b.serve_reference(seed, dims, lanes, n, 'bfloat16')]
    refs = b.serve_reference(seed, dims, lanes, n)
    b.print_rows(got, [t for t, _ in refs], [s for _, s in refs], 'control')
    return b.serve_comparisons(got, [t for t, _ in refs],
                               [s for _, s in refs], config['correct'])


def recording_rows(path):
    """Every lane's rows' own relative L2 (to the reference at the
    program's precision and at highest), in the order print_rows is
    called, written to `path` as JSON when the tool ends: what a
    steadier number than the worst lane's is chosen from."""
    import json
    from builders import solar_open2 as b
    kept, print_rows = [], b.print_rows

    def recorded(got, truth, same, who='program'):
        kept.append({'who': who, 'lanes': [
            {'same': b.row_errors(g, s_).tolist(),
             'highest': b.row_errors(g, t).tolist()}
            for g, t, s_ in zip(got, truth, same)]})
        return print_rows(got, truth, same, who)

    def write():
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, 'w') as f:
            json.dump(kept, f)

    b.print_rows = recorded
    return write


def main(argv):
    readings_granite_h.reseed, readings_granite_h.control = reseed, control
    write = None
    if '--rows' in argv:
        at = argv.index('--rows')
        write = recording_rows(argv[at + 1])
        argv = argv[:at] + argv[at + 2:]
    try:
        return readings_granite_h.main(argv)
    finally:
        if write:
            write()


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
