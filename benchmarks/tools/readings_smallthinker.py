"""tools/readings_hybrid.py for the SmallThinker block: the readings the
limits of `correct` are set from, in one process and one set-up. For
each seed: the program's own check; the same check with the follow-up
opened on its document's full pages and ANOTHER document's window tail
(the third control: PrefixCache.match_window's window pages swapped for
those of the first filler's document); then the two controls that need
no program, the reference in the program's place on lanes of the check's
lengths: computed in bfloat16 storage (prec 'bfloat16'), and with the
window ignored in the sliding layers (full_window). Each control has to
come out as NOT correct by at least one limit in every seed.

    python benchmarks/tools/readings_smallthinker.py \\
        --workload sthink21b_serve_mixed --seeds 1,2,3 [--rehearse]

A seed changes the weights as well as the inputs: each seed's tensors go
straight into the decoder's weight scope (a private attribute: a tool
may, a judged run never does). The corpus is cached before every check,
each document alone through the decoder, as set-up caches it through the
engine (a check ends by emptying the cache): a document's window tail
is resident only where a prompt ENDED on the document, so a filler that
met it first behind another's question would prefill it cold.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def reseed(system, seed):
    from builders import smallthinker as b
    system.seed = int(seed)
    spec = system.dec._pair.spec
    scope = system.dec._weight_scope
    for name in spec.param_names():
        scope.find_var(name).delete()
    b.put_seeded_weights(scope, spec, system.dims, seed)


def cache_corpus(system):
    """Every document of the corpus once through the decoder and
    released: its pages of both pools stay in the prefix cache."""
    from harness import traffic_docs
    dec = system.dec
    for doc in traffic_docs.documents(system.traffic['params'],
                                      system.config):
        dec.open_stream(0, doc)
        while dec.prefill_step(0) is None:
            pass
        dec.release(0)


def wrong_tail(system):
    """system.check() in which the follow-up stream is handed its
    document's full pages and the window pages of the document the
    first filler opened on."""
    from builders import smallthinker as b
    from harness import traffic_docs
    dec = system.dec
    prompts = b.check_prompts(system.seed, system.dims,
                              system.config['correct'], dec.page_tokens)
    follow = len(prompts[b.LANES.index('followup')])
    other = list(traffic_docs.documents(system.traffic['params'],
                                        system.config)[0])
    swapped = []

    def patched(cache):
        match = cache.match_window

        def match_window(prompt, limit=None):
            pages, tokens, wpages, wfirst = match(prompt, limit)
            if len(prompt) == follow and wpages:
                theirs = match(other + [1], len(other))[2]
                wpages = (theirs * len(wpages))[-len(wpages):]
                swapped.append(len(wpages))
            return pages, tokens, wpages, wfirst
        cache.match_window = match_window

    # check() ends in dec.reset(), which makes a new cache: patch each
    reset = dec.reset

    def reset_and_patch():
        reset()
        patched(dec._prefix)
    patched(dec._prefix)
    dec.reset = reset_and_patch
    try:
        cache_corpus(system)
        checks = system.check()
    finally:
        del dec.reset
        dec.reset()
    if not swapped:
        raise RuntimeError('the follow-up never matched: nothing swapped')
    return checks


def controls(config, dims, seed):
    """{control: the check's comparisons} with the reference, computed
    wrongly, in the program's place, on lanes of the check's lengths."""
    import numpy as np
    from builders import smallthinker as b
    sv, serving = config['correct'], config['serving']
    prompts = b.check_prompts(seed, dims, sv, int(serving['page_tokens']))
    n = b.check_decoded(prompts, sv, int(serving['prefill_chunk']))
    rng = np.random.default_rng([int(seed), 10])
    lanes = [list(p) + list(rng.integers(1, dims.vocab, size=k))
             for p, k in zip(prompts, n)]
    refs = b.serve_reference(seed, dims, lanes, n)
    truth, same = [t for t, _ in refs], [s for _, s in refs]
    out = {}
    for name, kw in (('bfloat16', {'prec': 'bfloat16'}),
                     ('window_ignored', {'prec': 'float32_default',
                                         'full_window': True})):
        got = [g for g, in b.serve_reference(seed, dims, lanes, n, **kw)]
        out[name] = b.comparisons(got, truth, same, sv)
    return out


def _line(what, seed, checks):
    ok = all(c['value'] <= c['limit'] for c in checks)
    print('%s seed %d: %s -> %s' % (what, seed, ' '.join(
        '%s=%.6g%s' % (c['name'], c['value'],
                       '' if c['value'] <= c['limit'] else '(EXCEEDED)')
        for c in checks), 'correct' if ok else 'not correct'), flush=True)
    return ok


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--rehearse', action='store_true')
    ap.add_argument('--set', action='append', default=[],
                    metavar='group.key=json', help='override a config key')
    args = ap.parse_args(argv)
    import json
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
    for item in args.set:
        key, _, val = item.partition('=')
        group, _, key = key.partition('.')
        config[group][key] = json.loads(val)
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', runner.CACHE_DIR)
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    from paddle_tpu.obs import telemetry
    telemetry.enable()
    system = manifest.resolve(config['builder'])(
        config=config, traffic=traffic,
        devices=jax.devices()[:cell['chips']], seed=seeds[0],
        phases=setup_clock.Phases(time.time()), rehearse=args.rehearse)
    worst, caught = {}, {}
    try:
        for i, seed in enumerate(seeds):
            if i:
                reseed(system, seed)
            t0 = time.perf_counter()
            cache_corpus(system)
            checks = system.check()
            _line('program', seed, checks)
            print('  (%.1f s)' % (time.perf_counter() - t0), flush=True)
            for c in checks:
                worst[c['name']] = max(worst.get(c['name'], 0.0), c['value'])
            caught.setdefault('wrong_window_tail', []).append(
                not _line('control wrong_window_tail', seed,
                          wrong_tail(system)))
    finally:
        system.close()
    print('program, largest over %d seeds: %s' % (len(seeds), ' '.join(
        '%s=%.6g' % kv for kv in sorted(worst.items()))), flush=True)
    del system.dec
    import gc
    gc.collect()
    for seed in seeds:
        for name, checks in controls(config, system.dims, seed).items():
            caught.setdefault(name, []).append(
                not _line('control ' + name, seed, checks))
    for name, each in caught.items():
        print('control %s: not correct in %d of %d seeds'
              % (name, sum(each), len(each)), flush=True)
    return 0 if all(all(each) for each in caught.values()) else 1


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
