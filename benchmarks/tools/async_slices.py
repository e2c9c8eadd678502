"""Whose are the asynchronous slices and copies of a serving cell's
compiled programs? XLA's memory-space assignment fetches part of an
operand ahead of the instruction that reads it (`slice-start` /
`slice-done`, `copy-start` / `copy-done`); those instructions carry no
op's name, so the trace books their time as `hlo:slice-done`, beside
the op that waits for the bytes. This reads the compiled HLO after one
set-up (chip) and follows every such fetch to the first instruction
downstream that an op's scope names:

    python benchmarks/tools/async_slices.py --workload granite4hs_serve_sessions \\
        [--events FILE] [--out chiprun_out/async_slices.json]

Per compiled module: {kind of fetch: {consumer's op: [count, bytes]}}.
With --events, a file `benchmarks/run.py --trace 1 --record-trace FILE`
wrote in the same call, also the device seconds of each kind by
consumer's op, over the traced slice. The HLO texts go beside the
output, gzipped, for a reading by eye.
"""
import argparse
import bisect
import collections
import gzip
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

ASYNC = re.compile(r'^(slice|copy|dynamic-slice|all-gather)-(start|done)')
_LINE = re.compile(r'^\s*(?:ROOT )?%([\w.\-]+) = (.*)$')
_REF = re.compile(r'%([\w.\-]+)')
_SHAPE = re.compile(r'^\(*(\w+)\[([\d,]*)\]')
_WIDTH = {'f32': 4, 's32': 4, 'u32': 4, 'bf16': 2, 'f16': 2, 's8': 1,
          'u8': 1, 'pred': 1, 's64': 8, 'u64': 8, 'f64': 8}


def parse(text):
    """{instruction: (rest of its line, [operands], op label or None)}
    of one module's text; names inside fused computations too (they
    are unique within a module)."""
    from harness import trace
    out = {}
    for line in text.splitlines():
        m = _LINE.match(line)
        if not m:
            continue
        name, rest = m.groups()
        body = rest.split(', metadata=')[0]
        meta = re.search(r'op_name="([^"]+)"', rest)
        found = trace._SCOPE.findall(meta.group(1)) if meta else []
        out[name] = (body, [r for r in _REF.findall(body) if r != name],
                     found[-1] if found else None)
    return out


def nbytes(body):
    m = _SHAPE.match(body)
    if not m:
        return 0
    n = 1
    for d in filter(None, m.group(2).split(',')):
        n *= int(d)
    return n * _WIDTH.get(m.group(1), 4)


def consumers(instrs):
    """{async instruction, 'done' and its 'start' alike: the op label
    of the first labelled instruction downstream of the 'done' ('?'
    where none is)}."""
    users = collections.defaultdict(list)
    for name, (_, operands, _) in instrs.items():
        for o in operands:
            users[o].append(name)
    out = {}
    for name in instrs:
        if not ASYNC.match(name) or '-done' not in name:
            continue
        seen, front, label = {name}, [name], None
        while front and label is None:
            nxt = []
            for f in front:
                for u in users.get(f, ()):
                    if u in seen:
                        continue
                    seen.add(u)
                    if instrs[u][2]:
                        label = instrs[u][2]
                        break
                    nxt.append(u)
                if label:
                    break
            front = nxt
        out[name] = label or '?'
        for start in instrs[name][1]:       # its own start, by operand
            if ASYNC.match(start):
                out[start] = out[name]
    return out


def table(instrs, whose):
    out = collections.defaultdict(lambda: collections.defaultdict(
        lambda: [0, 0]))
    for name, label in whose.items():
        if '-done' not in name:
            continue
        kind = ASYNC.match(name).group(1)
        cell = out[kind][label]
        cell[0] += 1
        cell[1] += nbytes(instrs[name][0])
    return {k: dict(v) for k, v in out.items()}


def seconds_by_consumer(record, parsed):
    """Device seconds of the traced slice's async instructions by
    (kind-phase, consumer's op); each traced module takes the text that
    holds the most of the instructions seen running inside it."""
    from harness import trace
    events = [tuple(e) for e in record['events']]
    first = min(e[0] for e in events if e[0].startswith('/device:'))
    ops = sorted((e[3], trace._instr(e[2]), e[4]) for e in events
                 if e[0] == first and e[1] == trace.OPS_LINE)
    starts = [o[0] for o in ops]
    out = collections.defaultdict(float)
    best_of = {}
    for ev in events:
        if ev[0] != first or ev[1] != trace.MODULES_LINE:
            continue
        lo = bisect.bisect_left(starts, ev[3])
        hi = bisect.bisect_right(starts, ev[3] + ev[4])
        inside = ops[lo:hi]
        if ev[2] not in best_of:
            seen = {i for _, i, _ in inside}
            best_of[ev[2]] = max(
                parsed, key=lambda p: len(seen & set(p[0])), default=None)
        instrs, whose = best_of[ev[2]]
        for _, instr, dur in inside:
            m = ASYNC.match(instr)
            if not m:
                continue
            out['%s-%s %s' % (m.group(1), m.group(2),
                              whose.get(instr, '?'))] += dur / 1e9
    return dict(out)


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=11)
    ap.add_argument('--events')
    ap.add_argument('--out', default='chiprun_out/async_slices.json')
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    from harness import manifest, runner, setup_clock
    man = manifest.check(manifest.load())
    cell, cfg_entry = manifest.cell(man, args.workload)
    config = manifest.read_json(cfg_entry['file'])
    traffic = manifest.read_json(manifest.traffic_file(man, cell['traffic']))
    if args.rehearse:
        runner._env_for_rehearsal(1)
        config = runner._overlaid(config, config['rehearse'])
        traffic['params'].update(traffic['rehearse'])
    os.environ.setdefault('JAX_COMPILATION_CACHE_DIR', runner.CACHE_DIR)
    import jax
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    system = manifest.resolve(config['builder'])(
        config=config, traffic=traffic, devices=jax.devices()[:1],
        seed=args.seed, phases=setup_clock.Phases(time.time()),
        rehearse=args.rehearse)
    try:
        system.warm_up(None)
        texts = system.hlo_texts()
    finally:
        system.close()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out + '.hlo.txt.gz', 'wt') as f:
        f.write('\n\n'.join(texts))
    parsed = []
    result = {'modules': []}
    for text in texts:
        instrs = parse(text)
        whose = consumers(instrs)
        parsed.append((instrs, whose))
        if whose:
            name = re.search(r'^HloModule (\S+)', text, re.M)
            result['modules'].append({
                'module': name.group(1).rstrip(',') if name else '?',
                'labels': sorted({v[2] for v in instrs.values() if v[2]}),
                'fetches': table(instrs, whose)})
    if args.events:
        with open(args.events) as f:
            result['slice_seconds'] = seconds_by_consumer(json.load(f),
                                                          parsed)
    with open(args.out, 'w') as f:
        json.dump(result, f, indent=1)
    for m in result['modules']:
        print(m['module'], json.dumps(m['fetches']))
    print('seconds', json.dumps(result.get('slice_seconds')))


if __name__ == '__main__':
    main(sys.argv[1:])
