"""Every cell walks end to end on the CPU (run.py --rehearse), a cell made
only of new files runs with no edit to a file that is there, and the
control of `correct` comes out as not correct."""
import json
import os
import subprocess
import sys

import pytest

from harness import manifest, runner
from tools import controls

ROOT = manifest.ROOT
RESULT_KEYS = {'correct', 'attempted', 'failed', 'metrics', 'device'}


def _run(*args):
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmarks', 'run.py'), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines + proc.stderr.splitlines()


@pytest.mark.parametrize('cell', [w['name'] for w in
                                  manifest.load()['workloads']])
def test_cell_rehearses(cell):
    line, lines = _run('--workload', cell, '--seed', str(2**31 + 5),
                       '--seconds', '2', '--trace', '1', '--rehearse')
    assert RESULT_KEYS <= set(line)
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0
    assert line['device']['platform'] == 'cpu' and line['rehearsal'] is True
    chips = next(w['chips'] for w in manifest.load()['workloads']
                 if w['name'] == cell)
    assert line['device']['count'] == chips
    # no timing is written under a metric's name off the chip: counts only
    sources = {m['name']: m['source'] for m in manifest.load()['per_layer']}
    assert line['metrics'] and all(
        sources[k] == 'program_counter' for k in line['metrics'])
    # and every count the cell's manifest entry lists is on the line: the
    # driver refuses a traced run that lacks one
    # the runner names what the whole line (before timings are dropped for
    # the rehearsal) lacks of the cell's listed metrics: the driver refuses
    # a traced run that lacks one. Off the chip only the trace's may lack.
    lacks = [n.strip(',') for l in lines if l.startswith('the result line '
             'lacks ') for n in l.split(', which')[0].split()[4:]]
    # (and mfu, a share of the chip's peak, which no CPU has)
    assert all(sources[n] == 'device_trace' or n.startswith('mfu.')
               for n in lacks), lacks
    assert not manifest.lacking(manifest.load(), 'per_layer', cell,
                                set(line['metrics']) | {
        n for n, s in sources.items() if s != 'program_counter'})
    assert any(l.startswith('compared ') and '(limit' in l for l in lines)
    phases = next(l for l in lines if l.startswith('setup phases '))
    parts = dict(p.split('=') for p in phases.split()[2:])
    assert sum(float(parts[p]) for p in
               ('init', 'build', 'weights', 'load', 'warm')) == \
        pytest.approx(float(parts['total']), abs=0.01)


def test_a_cell_made_only_of_new_files_runs():
    """tests/toy/ holds a manifest whose one cell is a new configuration
    file, a new traffic file and a new layer-metric reader, beside the
    benchmark's own paths: nothing that is there was edited for it."""
    line, _ = _run('--manifest', 'benchmarks/tests/toy/BENCHMARK.json',
                   '--workload', 'toy_cell', '--seed', '3', '--seconds', '1',
                   '--trace', '1', '--rehearse')
    assert line['correct'] is True
    assert line['metrics']['toy_steps_run.train']['value'] == \
        line['attempted'] > 0
    assert line['metrics']['compiles_in_window.train']['value'] == 0


def test_no_accelerator_no_result():
    """Off the chip, without --rehearse: another exit code than 0 and no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, 'benchmarks', 'run.py'),
         '--workload', 'gpt1b3_train', '--seed', '1', '--seconds', '1',
         '--trace', '0'], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


@pytest.mark.parametrize('name', ['train', 'serve'])
def test_the_control_is_not_correct(name):
    """The reference one precision down, in the program's place, at a
    size a test run can hold (the configuration's widths cut to the
    `rehearse` group, its limits as committed): on three seeds it must
    miss a limit, and the reference itself must pass with room."""
    config = manifest.read_json(
        'benchmarks/configs/cerebras-gpt-1.3b-%s.json' % name)
    config = runner._overlaid(config, config['rehearse'])
    config = runner._overlaid(config, config.get('control_test', {}))
    for seed in (1, 2, 2**31 + 3):
        checks = controls.run_control(config, seed)
        assert any(c['value'] > c['limit'] for c in checks), checks
        sound = controls.run_control(config, seed, prec='float32')
        assert all(c['value'] * 10 <= c['limit'] for c in sound), sound


def test_the_training_probe_sees_a_missing_all_reduce():
    """The batch compared is `per_step` DISTINCT sequences, so under a dp
    mesh every chip holds other data: the gradient of one chip's share
    alone (what a step without its all-reduce would leave there) misses
    the limit against the mean over the whole batch."""
    from builders import gpt2 as b
    from reference import gpt2 as ref
    config = manifest.read_json(
        'benchmarks/configs/cerebras-gpt-1.3b-train.json')
    config = runner._overlaid(config, config['rehearse'])
    dims = ref.dims_of(config)
    seqs = b.train_probe(5, dims, 4)
    assert len({s.tobytes() for s in seqs}) == 4
    wanted = b.train_wanted(dims)
    whole = b.train_reference(5, dims, seqs, wanted)
    share = b.train_reference(5, dims, seqs[:1], wanted)
    checks = b.train_comparisons(share[0], share[1], whole[0], whole[1],
                                 config['correct'])
    assert all(c['value'] > c['limit'] for c in checks), checks


def test_the_serving_check_decodes_several_lanes_together():
    """The lanes differ in length, and the longest reaches the longest
    context chat_open can make (prompt + output), less the pad."""
    config = manifest.read_json(
        'benchmarks/configs/cerebras-gpt-1.3b-serve.json')
    mix = manifest.read_json('benchmarks/traffic/chat_open.json')['params']
    lens = config['correct']['prompt_tokens']
    assert len(set(lens)) == len(lens) >= 3
    longest = mix['prompt_tokens'][1] + mix['output_tokens'][1]
    assert longest - 16 <= max(lens) + config['correct']['decode_tokens'] \
        <= longest
