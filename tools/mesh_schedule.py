"""mesh_schedule.py: where XLA put the collectives of a mesh step, read off-chip.

Builds the language-model training step the four-chip cell runs (the
program of benchmarks/builders/gpt2.py TrainSystem.build: py_reader ->
trunk -> fused head -> mean -> AMP Adam) from a configuration file, hands
it to a ParallelExecutor over a DESCRIBED topology (jax.experimental.
topologies: the TPU compiler is installed, no chip is attached), compiles
the step's device segment with exactly the jit options the executor would
use on the chip, and lists every collective of the scheduled entry
computation in schedule order:

  kind      all-reduce, all-gather, reduce-scatter, collective-permute,
            all-to-all (a fusion(kind=kCustom) that calls an
            all-reduce-scatter computation, which is how the TPU compiler
            sums a gradient into a shard, is a reduce-scatter, plain,
            with the bytes of its operand: what crosses the links)
  form      plain   an instruction like any other: the chip does nothing
                    else while it runs
            marked  the same, carrying async_collective_name; measured on
                    the chip it blocks exactly as a plain one does
            pair    a -start and a -done instruction
            fused   an async collective fusion (async-collective-start,
                    compute fusions that carry its steps, -done): the one
                    form seen to run beside compute (PERF.md, PR 32)
  bytes     of the result, with the dtypes (of the operand for a
            reduce-scatter fusion)
  scope     the program op in the instruction's metadata (GSPMD gives a
            gradient's sum the scope of the op that produced it)
  carriers  for a fused one: the program ops of the compute fusions that
            carry it
  between   the compute instructions (fusions, custom calls, convolutions,
            while loops) scheduled between the collective and the first
            instruction that needs its result

Nothing runs and nothing is timed: a schedule is a count, never a speed.

  JAX_PLATFORMS=cpu python tools/mesh_schedule.py            # the cell
  ... --layers 2 --per-step 8                                # seconds
  ... --no-overlap                                           # XLA's default schedule
  ... --dump /root/scratch/step.hlo                          # the whole text
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
from unittest import mock

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

_DEFAULT_CONFIG = os.path.join(
    _ROOT, 'benchmarks', 'configs', 'cerebras-gpt-1.3b-train.json')

_KINDS = ('all-reduce', 'all-gather', 'reduce-scatter',
          'collective-permute', 'all-to-all')
_DTYPE_BYTES = {'pred': 1, 's8': 1, 'u8': 1, 'bf16': 2, 'f16': 2, 's16': 2,
                'u16': 2, 'f32': 4, 's32': 4, 'u32': 4, 'f64': 8, 's64': 8,
                'u64': 8}
_SHAPE = re.compile(r'\b(%s)\[([0-9,]*)\]' % '|'.join(_DTYPE_BYTES))
_INSTR = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\((.*)$')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r'(?:^|/)([a-z_0-9]+\.\d+)(?:/|$)')
_OPERAND = re.compile(r'%([\w.\-]+)')
_SCATTER_CALL = re.compile(r'calls=%?(all-reduce-scatter[\w.\-]*)')
# what the chip spends time on between a start and its done
_COMPUTE = ('fusion', 'custom-call', 'convolution', 'while', 'dot',
            'call', 'conditional')


def describe(topology):
    from jax.experimental import topologies
    return topologies.get_topology_desc(
        platform='tpu', topology_name=topology).devices


def build_lm_step(cfg, per_step, n_devices):
    """(main program, loss name): TrainSystem.build's program."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer as tfm
    fluid.flags.set_flags(cfg.get('flags', {}))
    t = int(cfg['n_positions'])
    tc = tfm.TransformerConfig(
        vocab=int(cfg['vocab_size']), dim=int(cfg['n_embd']),
        heads=int(cfg['n_head']), layers=int(cfg['n_layer']),
        ffn=int(cfg['n_inner']), max_len=t, use_tp=False, use_sp=False,
        flash_attention=True)
    opt_cfg = cfg.get('optimizer', {})
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 1
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        reader = fluid.layers.py_reader(
            capacity=4, shapes=[(-1, t, 1), (-1, t, 1)],
            dtypes=['int64', 'int64'], name='schedule_reader',
            use_double_buffer=True)
        tokens, labels = fluid.layers.read_file(reader)
        trunk = tfm.language_model_trunk(tokens, tc)
        cost = fluid.layers.fused_softmax_cross_entropy(
            trunk, labels, tc.vocab,
            chunk=min(int(opt_cfg.get('head_chunk', 4096)),
                      per_step * t // n_devices), name='lm_head')
        loss = fluid.layers.mean(cost)
        opt = fluid.optimizer.Adam(
            learning_rate=float(opt_cfg.get('learning_rate', 2e-4)))
        fluid.contrib.mixed_precision.decorate(opt).minimize(loss)
    return main, loss.name


def compile_step(program, fetch_names, devices, per_step, overlap=True,
                 replicated=False, xla=None, uncommitted=()):
    """The compiled device segment (the largest, where a program has
    several) of `program` under a ParallelExecutor over `devices`, which
    may be described and not attached: arguments are shapes with the
    shardings the executor would give the arrays (persistable state where
    ParallelExecutor.state_sharding puts it, everything else split over
    dp). overlap=False drops the executor's compiler options: XLA's
    default schedule, for comparison. replicated=True holds all state
    as replicas (every mesh step before PR 47), xla adds compiler
    options to the executor's: arms to compare, never what the chip
    runs. The variables named in uncommitted arrive with no sharding,
    as values a script has just put in the scope do."""
    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.executor import PreparedProgram, _DeviceSegment

    pe = fluid.ParallelExecutor(use_cuda=True, main_program=program,
                                devices=list(devices))
    if replicated:
        pe.state_sharding = \
            lambda name: pe._var_sharding(name) or pe._replicated
    prepared = PreparedProgram(program, 0, (), list(fetch_names))
    segment = max((s for s in prepared.steps
                   if isinstance(s, _DeviceSegment)),
                  key=lambda s: len(s.ops))
    block = prepared.block
    out_set = set(segment.out_names)

    def struct(name):
        var = block.vars[name]
        shape = tuple(per_step if d in (-1, None) else int(d)
                      for d in (var.shape or ()))
        dtype = jax.dtypes.canonicalize_dtype(np.dtype(var.dtype))
        if name in uncommitted:
            return jax.ShapeDtypeStruct(shape, dtype)
        if var.persistable:
            sharding = pe.state_sharding(name)
        else:
            sharding = pe._var_sharding(name) or (
                pe._batch_sharded if shape else pe._replicated)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    donated = {n: struct(n) for n in segment.in_names if n in out_set}
    const = {n: struct(n) for n in segment.in_names if n not in out_set}
    key = jax.ShapeDtypeStruct((2,), np.uint32, sharding=pe._replicated)
    platform = devices[0].platform
    # the emitters ask jax.default_backend() which lowering to take (the
    # Mosaic flash kernel on a TPU); here it says "cpu" whatever the
    # program is compiled for
    options = dict(pe._overlap_options() or {}, **(xla or {})) \
        if overlap else None
    with mock.patch.object(jax, 'default_backend', return_value=platform), \
            mock.patch.object(type(pe), '_overlap_options',
                              lambda self: options):
        jitted = pe._compile_segment(segment, block, program)
        return jitted.lower(donated, const, key).compile()


def _nbytes(text):
    total, dtypes = 0, collections.Counter()
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in dims.split(','):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
        dtypes[dtype] += n * _DTYPE_BYTES[dtype]
    return total, dtypes


def _scope(line):
    m = _OP_NAME.search(line)
    if not m:
        return '-'
    scopes = _SCOPE.findall(m.group(1))
    return scopes[-1] if scopes else m.group(1).rsplit('/', 1)[-1][:40]


def entry_instructions(hlo_text):
    """[(name, result type, opcode, whole line)] of the ENTRY computation,
    in the order printed: the schedule, for a module that says
    is_scheduled=true."""
    out, inside = [], False
    for line in hlo_text.splitlines():
        if line.startswith('ENTRY '):
            inside = True
            continue
        if inside:
            if line.startswith('}'):
                break
            m = _INSTR.match(line)
            if m:
                out.append((m.group(1), m.group(2), m.group(3), line))
    return out


def _computations(hlo_text):
    """{computation name: body text}."""
    out, name, body = {}, None, []
    for line in hlo_text.splitlines():
        if name is None:
            m = re.match(r'^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$', line)
            if m:
                name, body = m.group(1), []
        elif line.startswith('}'):
            out[name] = '\n'.join(body)
            name = None
        else:
            body.append(line)
    return out


def _uses(line, names):
    return not names.isdisjoint(_OPERAND.findall(line.split('(', 1)[1]))


def list_collectives(hlo_text):
    """One dict a collective of the scheduled entry computation, in
    schedule order: index, name, kind, form, bytes, dtypes, scope,
    between, and for a fused one carriers and done_index, for the others
    first_use (the module's docstring says what each is)."""
    comps = _computations(hlo_text)
    instrs = entry_instructions(hlo_text)
    rows, open_fused = [], {}

    def held(line):
        """The collective a fusion's called computations hold, or None."""
        called = [comps.get(c, '')
                  for c in re.findall(r'calls=%?([\w.\-]+)', line)]
        return next((k for k in _KINDS for body in called
                     if ' %s(' % k in body or ' %s-start(' % k in body),
                    None)

    for i, (name, rtype, opcode, line) in enumerate(instrs):
        base = next((k for k in _KINDS if opcode in
                     (k, k + '-start', k + '-done')), None)
        if base and not opcode.endswith('-done'):
            # what a sum moves is its result; a start's result is a tuple
            # that repeats the operands beside the results
            nbytes, dtypes = _nbytes(rtype)
            form = 'plain'
            if opcode.endswith('-start'):
                form = 'pair'
                nbytes, dtypes = nbytes // 2, collections.Counter(
                    {k: v // 2 for k, v in dtypes.items()})
            elif 'async_collective_name' in line:
                form = 'marked'
            rows.append({'index': i, 'name': name, 'kind': base,
                         'form': form, 'bytes': nbytes,
                         'dtypes': dict(dtypes), 'scope': _scope(line)})
        elif opcode == 'fusion' and 'kind=kCustom' in line and \
                (call := _SCATTER_CALL.search(line)):
            # the sum of a whole operand that leaves as this chip's
            # shard: blocking, and no async-collective-* pair around it
            body = comps.get(call.group(1), '')
            operand = next((ln for ln in body.splitlines()
                            if ' parameter(0)' in ln), rtype)
            nbytes, dtypes = _nbytes(operand.split(' parameter(')[0])
            rows.append({'index': i, 'name': name, 'kind': 'reduce-scatter',
                         'form': 'plain', 'bytes': nbytes,
                         'dtypes': dict(dtypes), 'scope': _scope(line)})
        elif opcode == 'fusion' and name.startswith('async-collective-'):
            tag = name.split('.', 1)[1] if '.' in name else ''
            if name.startswith('async-collective-start'):
                open_fused[tag] = {
                    'index': i, 'name': name, 'form': 'fused',
                    'kind': held(line) or 'all-reduce',
                    'carriers': collections.Counter()}
            elif tag in open_fused:
                row = open_fused.pop(tag)
                nbytes, dtypes = _nbytes(rtype)
                row.update(bytes=nbytes, dtypes=dict(dtypes),
                           scope=_scope(line), done_index=i,
                           carriers=dict(row['carriers']))
                rows.append(row)
        elif opcode == 'fusion' and open_fused and held(line):
            for row in open_fused.values():
                row['carriers'][_scope(line)] += 1
    rows.sort(key=lambda r: r['index'])
    for row in rows:
        names, between = {row['name']}, collections.Counter()
        end = row.get('done_index')
        for name, _, opcode, line in instrs[row['index'] + 1:end]:
            if end is None and _uses(line, names):
                if opcode in ('get-tuple-element', 'bitcast', 'tuple') or \
                        opcode == row['kind'] + '-done':
                    names.add(name)      # a view or the done, not a use
                    continue
                row['first_use'] = {'name': name, 'opcode': opcode,
                                    'scope': _scope(line)}
                break
            if opcode in _COMPUTE:
                between[_scope(line)] += 1
        row['between'] = dict(between)
    return rows


def parse_options(pairs):
    """{name: value} of NAME=VALUE strings: true, false and whole numbers
    as such, anything else as text."""
    return {k: json.loads(v) if v in ('true', 'false') or v.isdigit() else v
            for k, v in (kv.split('=', 1) for kv in pairs)}


def summarize(rows):
    by_form, by_dtype = collections.Counter(), collections.Counter()
    by_kind = collections.defaultdict(collections.Counter)
    for r in rows:
        by_form[r['form']] += r['bytes']
        by_kind[r['kind']][r['form']] += r['bytes']
        for k, v in r['dtypes'].items():
            by_dtype[k] += v
    return {'collectives': len(rows), 'bytes_by_form': dict(by_form),
            'bytes_by_dtype': dict(by_dtype),
            'bytes_by_kind_and_form': {k: dict(v)
                                       for k, v in by_kind.items()}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--config', default=_DEFAULT_CONFIG,
                    help='a training configuration of benchmarks/configs')
    ap.add_argument('--per-step', type=int, default=16,
                    help='sequences a step over all chips')
    ap.add_argument('--layers', type=int, default=None,
                    help='override n_layer (2 compiles in seconds)')
    ap.add_argument('--topology', default='v5e:2x2')
    ap.add_argument('--no-overlap', action='store_true',
                    help="XLA's default schedule: the executor's compiler "
                         'options left out')
    ap.add_argument('--replicated', action='store_true',
                    help='all state held as replicas (every mesh step '
                         'before PR 47), for comparison')
    ap.add_argument('--xla', action='append', default=[], metavar='NAME=VALUE',
                    help="a compiler option beside the executor's, for "
                         'comparison (repeatable)')
    ap.add_argument('--dump', default=None,
                    help='write the compiled module text here')
    ap.add_argument('--json', action='store_true', help='rows as JSON lines')
    args = ap.parse_args(argv)

    with open(args.config) as f:
        cfg = json.load(f)
    if args.layers is not None:
        cfg['n_layer'] = args.layers
    devices = describe(args.topology)
    program, loss = build_lm_step(cfg, args.per_step, len(devices))
    xla = parse_options(args.xla)
    compiled = compile_step(program, [loss], devices, args.per_step,
                            overlap=not args.no_overlap,
                            replicated=args.replicated, xla=xla)
    text = compiled.as_text()
    if args.dump:
        with open(args.dump, 'w') as f:
            f.write(text)
    rows = list_collectives(text)
    for r in rows:
        if args.json:
            print(json.dumps(r))
            continue
        dt = '+'.join('%s %.1f MB' % (k, v / 1e6)
                      for k, v in sorted(r['dtypes'].items()))
        line = '%6d  %-18s %-6s %9.1f MB  %-26s %s' % (
            r['index'], r['kind'], r['form'], r['bytes'] / 1e6, dt,
            r['scope'])
        n = sum(r['between'].values())
        top = ', '.join('%s x%d' % kv for kv in
                        sorted(r['between'].items(),
                               key=lambda kv: -kv[1])[:4])
        use = r.get('first_use', {})
        if r['form'] == 'fused':
            line += '  | carried by %s; %d compute before its done' % (
                ', '.join('%s x%d' % kv
                          for kv in sorted(r['carriers'].items())), n)
        else:
            line += '  | %d compute before %s (%s)%s' % (
                n, use.get('opcode', 'the end'), use.get('scope', '-'),
                ': ' + top if top else '')
        print(line)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    print(json.dumps(dict(
        summarize(rows), topology=args.topology, n_layer=cfg['n_layer'],
        per_step=args.per_step, overlap=not args.no_overlap,
        replicated=args.replicated, xla=xla,
        flops_a_chip=cost.get('flops'),
        bytes_accessed_a_chip=cost.get('bytes accessed'),
        temp_bytes=getattr(mem, 'temp_size_in_bytes', None),
        argument_bytes=getattr(mem, 'argument_size_in_bytes', None))))
    return 0


if __name__ == '__main__':
    sys.exit(main())
