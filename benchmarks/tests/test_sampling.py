"""Every seed offers the same multiset of lengths and gaps, in another
order."""
import numpy as np
import pytest

from harness import manifest, sampling, traffic

MODEL = {'n_positions': 2048, 'vocab_size': 50257}
SEEDS = (0, 7, 2**31 + 11)


def _params(name):
    return manifest.read_json('benchmarks/traffic/%s.json' % name)['params']


def test_grids_are_fixed_and_spread():
    lens = sampling.log_uniform_lengths(64, 1024, 100)
    assert lens[0] >= 64 and lens[-1] <= 1024 and (np.diff(lens) >= 0).all()
    # log-uniform: the median is the geometric mean of the ends
    assert abs(np.median(lens) - 256) < 8
    gaps = sampling.exponential_gaps(2.5, 112)
    assert gaps.mean() == pytest.approx(1 / 2.5, rel=1e-12)
    assert gaps.sum() == pytest.approx(112 / 2.5, rel=1e-12)


@pytest.mark.parametrize('mix', ['chat_open'])
def test_same_multiset_other_order(mix):
    gen = manifest.resolve(manifest.read_json(
        'benchmarks/traffic/%s.json' % mix)['generator'])
    plans = [gen(_params(mix), s, MODEL, 45) for s in SEEDS]

    def lengths(plan):
        return ([len(r['prompt']) for r in plan['requests']],
                [r['max_new'] for r in plan['requests']])
    ref_p, ref_o = lengths(plans[0])
    for plan in plans[1:]:
        p, o = lengths(plan)
        assert sorted(p) == sorted(ref_p) and sorted(o) == sorted(ref_o)
        assert p != ref_p and o != ref_o
        assert sum(p) == sum(ref_p) and sum(o) == sum(ref_o)
    assert all(len(r['prompt']) + r['max_new'] <= MODEL['n_positions']
               for plan in plans for r in plan['requests'])
    # token ids come from the seed: same seed same ids, another seed others
    again = gen(_params(mix), SEEDS[1], MODEL, 45)
    assert all((a['prompt'] == b['prompt']).all() for a, b in
               zip(again['requests'], plans[1]['requests']))
    assert not (plans[0]['requests'][0]['prompt'][:8]
                == plans[2]['requests'][0]['prompt'][:8]).all()


def test_open_loop_gaps_same_multiset_and_rate():
    params = _params('chat_open')
    plans = [traffic.open_loop(params, s, MODEL, 45) for s in SEEDS]
    n = plans[0]['judged']
    assert n == round(params['rate_rps'] * 45)

    def gaps(plan):
        due = [r['due'] for r in plan['requests'][:n + 1]]
        return np.diff(due)
    g0 = gaps(plans[0])
    for plan in plans[1:]:
        g = gaps(plan)
        assert np.allclose(np.sort(g), np.sort(g0), rtol=0, atol=1e-9)
        assert not np.allclose(g, g0)
        # the judged requests are all due inside the window, from 0
        assert plan['requests'][0]['due'] == 0.0
        assert plan['requests'][n - 1]['due'] < 45
        assert plan['requests'][n]['due'] == \
            pytest.approx(n / params['rate_rps'])


def test_train_batches_differ_by_step_and_seed():
    params = _params('pretrain_2k')
    plan = traffic.train_batches(params, 5, MODEL, 45)
    stream = traffic.batch_stream(plan)
    a, b = next(stream), next(stream)
    assert a[0].shape == (params['per_step'], params['seq_len'], 1)
    assert not (a[0] == b[0]).all()
    assert (a[1] == np.roll(a[0], -1, axis=1)).all()
    other = next(traffic.batch_stream(
        traffic.train_batches(params, 6, MODEL, 45)))
    assert not (other[0] == a[0]).all()
    same = next(traffic.batch_stream(plan))
    assert (same[0] == a[0]).all()
