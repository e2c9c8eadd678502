"""The SmallThinker configuration's yardstick on the CPU:
costs_smallthinker against a hand count at the published sizes, the mixed
generator's design (two classes in one queue, the fixed multiset, the
seed's part), each new reader's arithmetic on made-up plain data (and
nothing, without a raise, on a run that lacks the counters), the
accepted readers on this cell's file as it stands, the file against the
catalog's rules, the check's prompts, and the three controls at the
rehearse widths: each has to come out as not correct."""
import collections
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import (costs_axk1, costs_smallthinker as costs, manifest,
                     runner, traffic_docs, traffic_mixed)

CELL = 'sthink21b_serve_mixed'
NEW = ['window_attn_share.tpot', 'paged_attn_window_roofline.tpot',
       'paged_attn_kv4_roofline.tpot', 'sthink_decode_hbm_roofline.tpot',
       'window_pages_freed.tpot', 'window_live_pages_max.tpot',
       'prefix_window_tail_miss.tpot']
ACCEPTED = ['moe_share.tpot', 'moe_pairs_per_expert.tpot',
            'moe_experts_touched_share.tpot',
            'moe_gated_expert_roofline.tpot', 'prefix_reuse_share.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


@pytest.fixture(scope='module')
def mix():
    return manifest.read_json('benchmarks/traffic/mixed_docs_chat_open.json')


def test_published_sizes_by_hand(config):
    d = 2560
    # q 2560 x 3584 = 9.175 M, k + v 2 x 2560 x 512 = 2.621 M, o 9.175 M
    assert d * 28 * 128 == 9_175_040 and 2 * d * 4 * 128 == 2_621_440
    assert costs.attention_params(config) == 2 * 9_175_040 + 2_621_440 \
        == 20_971_520
    assert costs.router_params(config) == d * 64 == 163_840
    assert costs.expert_params(config) == 3 * d * 768 == 5_898_240
    assert 64 * 5_898_240 == 377_487_360
    layer = 20_971_520 + 163_840 + 377_487_360 + 2 * d
    assert costs.layer_params(config) == layer
    assert round(layer / 1e6, 2) == 398.63
    assert round(4 * layer / 1e6, 1) == 1594.5
    assert 2 * 151936 * d == 777_912_320
    assert costs.param_count(config) == 4 * layer + 777_912_320 + d
    assert round(costs.param_count(config) / 1e9, 3) == 2.372
    assert round(costs.weight_bytes(config) / 1e9, 2) == 9.49
    # the published model: 52 layers
    whole = dict(config, num_hidden_layers=52)
    assert round(costs.param_count(whole) / 1e9, 1) == 21.5
    assert costs.layers(config) == (1, 3) and costs.layers(whole) == (13, 39)
    # K/V: 4 heads x 128 x 2 x 4 B a token a layer; a page of 16 tokens
    assert costs.kv_bytes_per_token(config) == 4096
    assert costs.attention_bytes(config, 16) == 65_536
    sv = config['serving']
    pools = (sv['kv_pages'] + 3 * sv['window_pages']) * 65_536
    assert sv['kv_pages'] * 65_536 / 1e9 == pytest.approx(1.07, abs=0.01)
    held = costs.weight_bytes(config) + pools
    assert 11e9 < held < 14.5e9                          # of 16: chips_layout
    # a stream at 12 k tokens: 48 MB in the full layer, 16 MB in each
    # sliding layer; with one table for all layers 192 MB
    assert 12_000 * 4096 / 1e6 == pytest.approx(49.2, abs=0.1)
    assert costs.window_rows(config, 12_000) == 4096
    assert costs.window_rows(config, 100) == 101
    assert 4096 * 4096 == 16_777_216
    # a decode step: every weight but the embedding and the experts not
    # chosen, the rows of both kinds
    outside = 4 * (20_971_520 + 163_840 + 2 * d) + 151936 * d + d
    assert costs.decode_step_bytes(config, 250_000, 90_000, 60.5) == \
        4 * outside + 4 * 4 * 60.5 * 5_898_240 \
        + (250_000 + 3 * 90_000) * 4096
    # 30 lanes that touch every expert: 8.3 GB of weights a step
    assert costs.decode_step_bytes(config, 0, 0, 64) / 1e9 == \
        pytest.approx(7.93, abs=0.01)


def test_the_accepted_cost_functions_read_this_file_as_it_stands(config):
    assert costs_axk1.layers(config) == (0, 4)
    assert costs_axk1.expert_params(config) == costs.expert_params(config)
    assert config['moe_intermediate_size'] == config['moe_ffn_hidden_size']
    assert config['n_routed_experts'] == config['moe_num_primary_experts']


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'paged_window_attention': 0.24, 'paged_attention': 0.16,
           'moe_experts': 1.1, 'mul': 0.3}
    programs = {'decode': {'calls': 200, 'device_s': 1.8},
                'prefill': {'calls': 20, 'device_s': 0.2}}
    counters = {'decode_calls': 2000, 'prefill_calls': 200,
                'moe_layer_calls': 8000, 'moe_pairs': 1_400_000,
                'moe_experts_touched': 480_000,
                'moe_prefill_layer_calls': 800,
                'moe_prefill_pairs': 800 * 200 * 6,
                'moe_prefill_experts_touched': 800 * 64,
                'prefix_tokens_reused': 900, 'prompt_tokens_admitted': 1000,
                'window_pages_freed': 4321, 'window_live_pages_max': 3900,
                'prefix_window_tail_miss': 0,
                'slice_decode_calls': 200,
                'slice_rows_read': 200 * 250_000,
                'slice_window_rows_read': 200 * 90_000,
                'slice_plain_decode_calls': 200,
                'slice_plain_rows_read': 200 * 250_000,
                'slice_plain_window_rows_read': 200 * 90_000,
                'slice_moe_layer_calls': 800, 'slice_moe_pairs': 800 * 175,
                'slice_moe_experts_touched': 800 * 60,
                'slice_moe_prefill_layer_calls': 80,
                'slice_moe_prefill_pairs': 80 * 1200,
                'slice_moe_prefill_experts_touched': 80 * 64}
    run = _run(config, ops, programs, counters,
               {'paged_window_attention': 200, 'paged_attention': 200,
                'moe_experts': 220})
    assert _read('window_attn_share.tpot', run) == pytest.approx(12.0)
    assert _read('paged_attn_window_roofline.tpot', run) == pytest.approx(
        100 * (200 * 3 * 90_000 * 4096 / 819e9) / 0.24)
    assert _read('paged_attn_kv4_roofline.tpot', run) == pytest.approx(
        100 * (200 * 1 * 250_000 * 4096 / 819e9) / 0.16)
    need = costs.decode_step_bytes(config, 250_000, 90_000, 60)
    assert _read('sthink_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.009)
    assert _read('window_pages_freed.tpot', run) == 4321
    assert _read('window_live_pages_max.tpot', run) == 3900
    assert _read('prefix_window_tail_miss.tpot', run) == 0
    # the accepted readers, on this file
    assert _read('moe_share.tpot', run) == pytest.approx(55.0)
    assert _read('moe_pairs_per_expert.tpot', run) == \
        pytest.approx(1400 / 480)
    assert _read('moe_experts_touched_share.tpot', run) == pytest.approx(
        100 * 60 / 64)
    dec = 200 * 4 * costs.expert_params(config) * 4 * 60 / 819e9
    pre = 20 * 4 * max(costs.expert_params(config) * 4 * 64 / 819e9,
                       2 * 1200 * costs.expert_params(config) / 197e12)
    assert _read('moe_gated_expert_roofline.tpot', run) == pytest.approx(
        100 * (dec + pre) / 1.1)
    assert _read('prefix_reuse_share.tpot', run) == pytest.approx(90.0)


def test_new_readers_find_nothing_on_a_run_without_the_counters(config):
    """A line of a program without a second table: no such op, span or
    counter. Nothing, no raise."""
    run = _run(config, {'mul': 1.5, 'paged_attention': 0.2},
               {'decode': {'calls': 100, 'device_s': 1.6}},
               {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100})
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_entries_follow_the_older_ones_and_the_cell_is_listed_where_it_reports():
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    at = names.index(NEW[0])
    assert at >= 75 and names[at:at + len(NEW)] == NEW
    for m in man['per_layer'][at:at + len(NEW)]:
        assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
        mod = manifest.layer_metric(man, m['name'])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == \
            (m['layer'], m['unit'], m['better'], m['source'])
    cells = [w['name'] for w in man['workloads']]
    assert cells.index(CELL) >= 7
    entry = man['workloads'][cells.index(CELL)]
    assert entry['chips'] == 1 and len(entry['why']) <= 200
    assert entry['traffic'] == 'mixed_docs_chat_open'
    assert [c['name'] for c in man['configs']].index(
        'smallthinker-21b-a3b-serve') >= 6
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert set(NEW) | set(ACCEPTED) <= listed
    # every metric that lists the five serving cells lists this one
    five = {'gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason',
            'axk1_serve_docfollow', 'granite4hs_serve_sessions'}
    for m in man['per_layer'] + man['end_to_end']:
        if five <= set(m.get('workloads', ())):
            assert CELL in m['workloads'], m['name']
    assert not listed & {'decode_hbm_roofline.tpot', 'gdn_share.tpot',
                         'ssm_share.tpot', 'mla_share.tpot',
                         'paged_attn_gqa_roofline.tpot',
                         'paged_attn_kv8_roofline.tpot'}


def test_the_file_keeps_every_published_key_but_the_reduced(config):
    """The catalog's rule: every number of the row's config under the
    same key; what differs is in `reduced` and is no width."""
    catalog = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.exists(catalog):
        pytest.skip('no catalog here')
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r['source_url'] == config['source'])
    differ = {k for k, v in row['config'].items() if config.get(k) != v}
    assert differ == set(config['reduced']) == {'num_hidden_layers'}
    assert config['published']['num_hidden_layers'] == 52
    assert config['n_positions'] == row['config']['max_position_embeddings']
    # the floors: a whole period (1 global : 3 sliding) and four layers
    n = config['num_hidden_layers']
    assert config['sliding_window_layout'][:n] == [0, 1, 1, 1]
    assert config['rope_layout'][:n] == [0, 1, 1, 1]
    assert {'window', 'attention', 'router', 'weights'} <= \
        set(config['assumed'])
    assert 'dtype' in config['departures']


# -- the traffic ------------------------------------------------------------------

def _multiset(plan):
    return sorted((r['class'], -1 if r['document'] is None
                   else r['document'], len(r['prompt']), r['max_new'])
                  for r in plan['requests'][:plan['judged']])


def test_two_classes_in_one_queue_and_one_design_for_every_seed(mix, config):
    p = mix['params']
    plans = [traffic_mixed.mixed(p, seed, config, 45.0)
             for seed in (7, 2**31 + 9, 3_000_000_011)]
    n = max(1, round(p['rate_rps'] * 45))
    docs = traffic_docs.documents(p, config)
    assert len(docs) == 16 and all(len(d) % 16 == 0 for d in docs)
    assert (len(docs[0]), len(docs[-1])) == (8192, 14336)
    for plan in plans:
        assert plan['judged'] == n and len(plan['requests']) == 2 * n
        window = plan['requests'][:n]
        kinds = collections.Counter(r['class'] for r in window)
        assert kinds['long'] == n // 2 and kinds['short'] == n - n // 2
        for r in window:
            assert len(r['prompt']) + r['max_new'] <= config['n_positions']
            if r['class'] == 'long':
                doc = docs[r['document']]
                assert (r['prompt'][:len(doc)] == doc).all()
                assert 64 <= len(r['prompt']) - len(doc) <= 256
                assert 256 <= r['max_new'] <= 768
            else:
                assert 128 <= len(r['prompt']) <= 1024
                assert 128 <= r['max_new'] <= 512
        # every part replays the seed's one deal, with ids of its own
        tail = plan['requests'][n:]
        assert [(r['class'], len(r['prompt']), r['max_new'])
                for r in window] == [(r['class'], len(r['prompt']),
                                      r['max_new']) for r in tail]
        assert np.allclose([r['due'] + 45.0 for r in window],
                           [r['due'] for r in tail])
        assert not any((x['prompt'][-8:] == y['prompt'][-8:]).all()
                       for x, y in zip(window, tail))
        assert all(0 <= r['due'] < 45.0 for r in window)
    assert _multiset(plans[0]) == _multiset(plans[1]) == _multiset(plans[2])
    assert [r['due'] for r in plans[0]['requests']] != \
        [r['due'] for r in plans[1]['requests']]
    again = traffic_mixed.mixed(p, 7, config, 45.0)
    assert all((x['prompt'] == y['prompt']).all() and x['due'] == y['due']
               for x, y in zip(plans[0]['requests'], again['requests']))
    # the two classes alternate down the design: any stretch of it holds
    # both in equal shares
    kinds = [k for k, _, _, _ in traffic_mixed.design(p, n)]
    assert all(a != b for a, b in zip(kinds, kinds[1:]))


# -- the check ---------------------------------------------------------------------

def test_the_check_s_prompts_by_hand(config):
    from builders import smallthinker as builder
    from reference import smallthinker as ref
    sv = config['correct']
    dims = ref.dims_of(config)
    assert (dims.layers, dims.sliding, dims.rope) == \
        (4, (0, 1, 1, 1), (0, 1, 1, 1))
    prompts = dict(zip(builder.LANES, builder.check_prompts(3, dims, sv, 16)))
    n = {k: len(v) for k, v in prompts.items()}
    assert n['short'] < dims.window - 512           # stays inside the window
    assert dims.window - 24 < n['edge'] < dims.window   # decode crosses it
    assert 13_000 < n['cold'] < 15_000 and n['cold'] % 16 and n['cold'] % 256
    assert n['parent'] % 16 == 8 and n['parent'] > 2 * dims.window
    assert n['followup'] == n['parent'] + sv['followup_tokens']
    assert (n['followup'] - 1) // 16 == n['parent'] // 16   # the same page
    assert (prompts['followup'][:n['parent']] == prompts['parent']).all()
    chunk = config['serving']['prefill_chunk']
    decoded = builder.check_decoded(list(prompts.values()), sv, chunk)
    assert decoded[-1] == sv['decode_tokens']
    assert n['edge'] + decoded[1] > dims.window + 64
    longest = max(k + d for k, d in zip(n.values(), decoded))
    assert longest <= config['n_positions']
    assert sv['filler_streams'] <= config['serving']['slots'] - 5
    # fillers run to the end too: a cached document, a question, a step
    # behind every chunk of every compared lane and the steps together
    assert 14336 + sv['filler_tokens'][1] + decoded[0] + 3 \
        <= config['n_positions']


def test_controls_without_a_program_read_over_the_limits(config):
    """At the rehearse widths, two periods deep (`control_test`): the
    reference in bfloat16 storage, and with the window ignored in the
    sliding layers, in the program's place, through the check's own
    comparisons: each is not correct."""
    from builders import smallthinker as builder
    from reference import smallthinker as ref
    small = runner._overlaid(config, config['rehearse'])
    small = dict(small, **config['control_test'])
    dims = ref.dims_of(small)
    assert dims.layers == 8 and dims.window == 32
    rng = np.random.default_rng(5)
    lanes = [list(rng.integers(1, dims.vocab, size=k)) for k in (24, 60, 120)]
    n = [4, 6, 8]
    seed = 2**31 + 7
    refs = builder.serve_reference(seed, dims, lanes, n)
    truth, same = [t for t, _ in refs], [s for _, s in refs]
    limits = small['correct']
    ok = builder.comparisons(same, truth, same, limits)
    assert all(c['value'] <= c['limit'] for c in ok)
    for kw in ({'prec': 'bfloat16'},
               {'prec': 'float32_default', 'full_window': True}):
        got = [g for g, in builder.serve_reference(seed, dims, lanes, n, **kw)]
        checks = builder.comparisons(got, truth, same, limits)
        assert any(c['value'] > c['limit'] for c in checks), kw


def test_the_third_control_needs_the_program_and_fails_too():
    """tools/readings_smallthinker.py at the rehearse widths, one seed:
    the program correct, and each control, the follow-up opened on
    another document's window tail among them, not correct."""
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, 'benchmarks', 'tools',
                                      'readings_smallthinker.py'),
         '--workload', CELL, '--seeds', str(2**31 + 11), '--rehearse'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert 'program seed %d' % (2**31 + 11) in out
    assert out.count('-> correct') == 1
    for name in ('wrong_window_tail', 'bfloat16', 'window_ignored'):
        assert 'control %s: not correct in 1 of 1 seeds' % name in out
