"""tools/readings_smallthinker.py for the SDAR block: the readings the
limits of `correct` are set from, in one process and one set-up. For
each seed: the program's own check (every compared lane's prefill row
and every pass's rows against the reference's full forward over the ids
the pass was fed), then the four controls, each the reference computed
WRONGLY in the program's place over the very passes the program made:
stored in bfloat16; the mask causal inside a block; the commit pass left
out; the follow-up's prefix adopted inside a block
(builders/sdar_moe.ServeSystem.controls). Each control has to come out
as NOT correct by at least one limit in every seed.

    python benchmarks/tools/readings_sdar.py \\
        --workload sdar30b_serve_blockgen --seeds 1,2,3 [--rehearse]

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
    from builders import sdar_moe as b
    system.seed = int(seed)
    spec = system.dec._pair.spec
    scope = system.dec._weight_scope
    for name in spec.param_names():
        scope.find_var(name).delete()
    b.put_seeded_weights(scope, spec, system.dims, seed)


def probe_line(system):
    """A check's passes through the builder's probe, beside what the
    program's own `paged.decode.tables` spans say of them and the steps
    whose `slot_tokens()` grew past their prompt (all that the base
    probe would name a lane)."""
    from harness import spans
    probe = system.probe
    before = (probe.decode_calls, probe.live_tokens,
              system.dec.block_stats()['passes'])
    t0 = time.perf_counter()
    grew = [0]
    seen = [system.dec.slot_tokens()]

    def watch():
        now = system.dec.slot_tokens()
        past = {s: n for s, n in seen[0].items()
                if n >= probe._prompt.get(s, 0)
                - probe._prompt.get(s, 0) % system.dec.block_tokens}
        grew[0] += sum(now.get(s, 0) > n for s, n in past.items())
        seen[0] = now
    probe.on_step.append(watch)
    system.check_run()
    probe.on_step.remove(watch)
    tables = [s for s in spans.program_spans()
              if s['name'] == 'paged.decode.tables' and s['t0'] >= t0]
    print('probe decode_calls=%d live_tokens=%d lanes=%d span_steps=%d '
          'span_live_tokens=%d span_lanes=%d grew_lanes=%d' % (
              probe.decode_calls - before[0],
              probe.live_tokens - before[1],
              system.dec.block_stats()['passes'] - before[2], len(tables),
              sum(s['live_tokens'] for s in tables),
              sum(s['block_rows'] for s in tables)
              // system.dec.block_tokens, grew[0]), flush=True)


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
    ap.add_argument('--control-test', action='store_true',
                    help='overlay the configuration\'s control_test group')
    ap.add_argument('--probe', action='store_true',
                    help='print what the step probe and the program\'s '
                         'spans count over the first seed\'s check')
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
    if args.control_test:
        config = dict(config, **config['control_test'])
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
    worst, least, caught = {}, {}, {}
    try:
        for i, seed in enumerate(seeds):
            if i:
                reseed(system, seed)
            t0 = time.perf_counter()
            if args.probe and not i:
                probe_line(system)
            run = system.check_run()
            truth = system.reference(run, 'float32')
            same = system.reference(run, 'float32_default')
            checks = system.compare(run, truth=truth, same=same)
            _line('program', seed, checks)
            print('  (%.1f s)' % (time.perf_counter() - t0), flush=True)
            for c in checks:
                worst[c['name']] = max(worst.get(c['name'], 0.0), c['value'])
            for name, got in system.controls(run, truth, same).items():
                caught.setdefault(name, []).append(
                    not _line('control ' + name, seed, got))
                for c in got:
                    key = (name, c['name'])
                    least[key] = min(least.get(key, float('inf')),
                                     c['value'])
            print('  (%.1f s with the controls)'
                  % (time.perf_counter() - t0), flush=True)
    finally:
        system.close()
    print('program, largest over %d seeds: %s' % (len(seeds), ' '.join(
        '%s=%.6g' % kv for kv in sorted(worst.items()))), flush=True)
    for name in caught:
        print('control %s, least over %d seeds: %s' % (
            name, len(seeds), ' '.join(
                '%s=%.6g' % (k[1], v) for k, v in sorted(least.items())
                if k[0] == name)), flush=True)
    for name, each in caught.items():
        print('control %s: not correct in %d of %d seeds'
              % (name, sum(each), len(each)), flush=True)
    return 0 if all(all(each) for each in caught.values()) else 1


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
