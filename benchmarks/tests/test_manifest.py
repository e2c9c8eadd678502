"""What the manifest check refuses, that the committed manifest passes
it, and that every layer metric's file agrees with its entry."""
import copy

import pytest

from harness import manifest


@pytest.fixture()
def man():
    return copy.deepcopy(manifest.load())


def test_committed_manifest_passes(man):
    manifest.check(man)
    assert man['paths'] == ['benchmarks']
    four = [w for w in man['workloads'] if w['chips'] == 4]
    assert len(four) <= max(1, len(man['workloads']) // 4)


@pytest.mark.parametrize('name', ['has space', 'a,b', 'a/b', '-lead', 'x' * 65,
                                  'muµ'])
def test_bad_names_are_refused(man, name):
    man['per_layer'][0]['name'] = name
    with pytest.raises(manifest.ManifestError):
        manifest.check(man)


@pytest.mark.parametrize('unit', ['tokens per second', 'x' * 17, '',
                                  'µs'])
def test_bad_units_are_refused(man, unit):
    man['end_to_end'][0]['unit'] = unit
    with pytest.raises(manifest.ManifestError):
        manifest.check(man)


def test_layer_metric_in_a_cell_without_its_end_to_end_metric(man):
    m = next(m for m in man['per_layer'] if m['name'] == 'mfu.train')
    m['workloads'] = ['gpt1b3_serve_chat']      # reports no train_items_s
    with pytest.raises(manifest.ManifestError):
        manifest.check(man)


def test_layer_metric_that_moves_nothing(man):
    man['per_layer'][0]['moves'] = 'no_such_metric'
    with pytest.raises(manifest.ManifestError):
        manifest.check(man)


def test_a_pair_of_configuration_and_traffic_twice(man):
    man['workloads'].append(dict(man['workloads'][0], name='the_same_again'))
    with pytest.raises(manifest.ManifestError):
        manifest.check(man)


def test_every_reader_agrees_with_its_entry(man):
    for m in man['per_layer']:
        mod = manifest.layer_metric(man, m['name'])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == \
            (m['layer'], m['unit'], m['better'], m['source']), m['name']
        assert callable(mod.read)


def test_a_split_quantity_has_one_reader(man):
    """`<base>.train` and `<base>.tpot` are read by the one `<base>.py`;
    a metric with a reader of its own name keeps it."""
    a = manifest.layer_metric(man, 'peak_hbm_gb.train')
    b = manifest.layer_metric(man, 'peak_hbm_gb.tpot')
    assert a.__file__ == b.__file__ and a.__file__.endswith('peak_hbm_gb.py')
    assert manifest.layer_metric(man, 'mfu.train').__file__.endswith(
        'mfu.train.py')
    with pytest.raises(manifest.ManifestError):
        manifest.layer_metric(man, 'no_such_metric.train')


def test_cells_find_their_files(man):
    for w in man['workloads']:
        cell, cfg = manifest.cell(man, w['name'])
        config = manifest.read_json(cfg['file'])
        traffic = manifest.read_json(manifest.traffic_file(man, w['traffic']))
        assert callable(manifest.resolve(config['builder']))
        assert callable(manifest.resolve(traffic['generator']))
        assert callable(manifest.resolve(traffic['drive']))
        assert config['reduced'] == cfg['reduced']
        assert manifest.metrics_of(man, 'per_layer', w['name'])
        assert len(manifest.metrics_of(man, 'end_to_end', w['name'])) >= 2
