"""BENCHMARK.json: reading it, finding each cell's files by name, and
the checks the contract makes before any run (names, units, that a
layer metric's cells report the end-to-end metric it moves)."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$')
_UNIT = re.compile(r'^[A-Za-z0-9_/%.\-]{1,16}$')
_SOURCES = ('device_trace', 'program_span', 'program_counter', 'host_clock')


class ManifestError(ValueError):
    pass


def load(path=None):
    with open(path or os.path.join(ROOT, 'BENCHMARK.json')) as f:
        return json.load(f)


def _need(cond, what):
    if not cond:
        raise ManifestError(what)


def check(man):
    """Raise ManifestError on what the contract refuses and this module
    can see; returns the manifest."""
    for group in ('configs', 'workloads', 'end_to_end', 'per_layer'):
        seen = set()
        for entry in man[group]:
            n = entry['name']
            _need(_NAME.match(n), 'name %r has a character outside '
                  'letters, digits, _ . - or is over 64' % (n,))
            _need(n not in seen, 'name %r appears twice in %s' % (n, group))
            seen.add(n)
    cells = {w['name']: w for w in man['workloads']}
    configs = {c['name'] for c in man['configs']}
    pairs = set()
    for w in man['workloads']:
        _need(w['config'] in configs, 'cell %r names no configuration'
              % w['name'])
        _need(_NAME.match(w['traffic']), 'traffic %r' % (w['traffic'],))
        _need(w['chips'] in (1, 4), 'cell %r: chips is 1 or 4' % w['name'])
        _need((w['config'], w['traffic']) not in pairs,
              'the pair %s x %s appears twice' % (w['config'], w['traffic']))
        pairs.add((w['config'], w['traffic']))
    e2e = {}
    for m in man['end_to_end']:
        _check_metric(m, cells)
        _need(m['source'] in ('host_clock', 'device_trace'),
              'end-to-end metric %r: source %r' % (m['name'], m['source']))
        e2e[m['name']] = set(m.get('workloads', cells))
    _need('setup_s' in e2e and e2e['setup_s'] == set(cells),
          'every cell reports setup_s')
    for m in man['per_layer']:
        _check_metric(m, cells)
        _need(m['moves'] in e2e, 'layer metric %r moves %r, which is no '
              'end-to-end metric' % (m['name'], m['moves']))
        for cell in m.get('workloads', e2e[m['moves']]):
            _need(cell in e2e[m['moves']], 'layer metric %r lists cell %r, '
                  'which does not report %r' % (m['name'], cell, m['moves']))
    return man


def _check_metric(m, cells):
    _need(_UNIT.match(m['unit']), 'unit %r of %r: 1 to 16 of letters, '
          'digits, _ / %% . -' % (m['unit'], m['name']))
    _need(m['better'] in ('lower', 'higher'), 'better of %r' % m['name'])
    _need(m['source'] in _SOURCES, 'source of %r' % m['name'])
    for cell in m.get('workloads', ()):
        _need(cell in cells, 'metric %r lists the unknown cell %r'
              % (m['name'], cell))


def cell(man, name):
    for w in man['workloads']:
        if w['name'] == name:
            cfg = next(c for c in man['configs'] if c['name'] == w['config'])
            return w, cfg
    raise ManifestError('no cell named %r in BENCHMARK.json' % (name,))


def metrics_of(man, group, cell_name):
    """The metrics of `group` this cell reports."""
    e2e = {m['name']: m.get('workloads') for m in man['end_to_end']}
    out = []
    for m in man[group]:
        cells = m.get('workloads')
        if cells is None and group == 'per_layer':
            cells = e2e[m['moves']]
        if cells is None or cell_name in cells:
            out.append(m)
    return out


def lacking(man, group, cell_name, metrics):
    """The metrics of `group` this cell lists and a result line's
    `metrics` lacks: the driver refuses a line that lacks one."""
    return [m['name'] for m in metrics_of(man, group, cell_name)
            if m['name'] not in metrics]


def read_json(rel_path):
    with open(os.path.join(ROOT, rel_path)) as f:
        return json.load(f)


def traffic_file(man, traffic):
    for base in man['paths']:
        p = os.path.join(base, 'traffic', traffic + '.json')
        if os.path.exists(os.path.join(ROOT, p)):
            return p
    raise ManifestError('no traffic file %s.json under %s'
                        % (traffic, man['paths']))


def resolve(spec):
    """'module:function' under benchmarks/ -> the function."""
    mod, _, fn = spec.partition(':')
    return getattr(importlib.import_module(mod), fn)


def layer_metric(man, name):
    """The reader module of one per-layer metric, found by its name:
    `<name>.py`, or, for a quantity split by the end-to-end metric it
    moves (`<base>.<suffix>`), the one `<base>.py` that reads all its
    splits. What a split moves is in its `per_layer` entry."""
    for base in man['paths']:
        for stem in (name, name.rpartition('.')[0]):
            p = os.path.join(ROOT, base, 'layer_metrics', stem + '.py')
            if stem and os.path.exists(p):
                spec = importlib.util.spec_from_file_location(
                    'layer_metric_' + re.sub(r'\W', '_', stem), p)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod
    raise ManifestError('no reader %s.py under layer_metrics/' % (name,))
