"""The SDAR configuration's yardstick on the CPU: costs_sdar against a
hand count at the published sizes, each new reader's arithmetic on
made-up plain data (and nothing, without a raise, on a run that lacks
the counters), the accepted readers on this cell's file as it stands,
the file against the catalog's rules, the entries' places, the traffic
file, the check's prompts, the step probe against the program's spans
over a rehearsal, and the four controls at the `control_test` widths:
each has to come out as not correct."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import costs_axk1, costs_sdar as costs, manifest, runner

CELL = 'sdar30b_serve_blockgen'
NEW = ['block_passes_per_token.tpot', 'block_commit_share.tpot',
       'block_masked_rows_share.tpot', 'block_attn_roofline.tpot',
       'sdar_block_hbm_roofline.tpot']
ACCEPTED = ['moe_share.tpot', 'moe_pairs_per_expert.tpot',
            'moe_experts_touched_share.tpot',
            'moe_gated_expert_roofline.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


def test_published_sizes_by_hand(config):
    d = 2048
    # q 2048 x 4096 = 8.389 M, k + v 2 x 2048 x 512 = 2.097 M, o 8.389 M
    assert d * 32 * 128 == 8_388_608 and 2 * d * 4 * 128 == 2_097_152
    assert costs.attention_params(config) == 2 * 8_388_608 + 2_097_152 + 256
    assert costs.router_params(config) == d * 128 == 262_144
    assert costs.expert_params(config) == 3 * d * 768 == 4_718_592
    assert 16 * 4_718_592 == 75_497_472
    layer = 18_874_624 + 262_144 + 75_497_472 + 2 * d
    assert costs.layer_params(config) == layer
    assert round(layer / 1e6, 2) == 94.64
    assert 2 * 18992 * d == 77_791_232
    assert costs.param_count(config) == 24 * layer + 77_791_232 + d
    assert round(costs.param_count(config) / 1e9, 3) == 2.349
    assert round(costs.weight_bytes(config) / 1e9, 2) == 9.40
    # the published model: 48 layers of 128 experts, the whole vocabulary
    whole = dict(config, num_hidden_layers=48, num_experts=128,
                 vocab_size=151936)
    assert round(costs.layer_params(whole) / 1e6, 1) == 623.1
    assert round(costs.param_count(whole) / 1e9, 1) == 30.5
    # a deployment chip in the published bfloat16: all 48 layers' share
    assert round(2 * (48 * layer + 77_791_232) / 1e9, 1) == 9.2
    # K/V: 2 x 4 heads x 128 x 4 B a token a layer
    assert costs.kv_bytes_per_token(config) == 4096
    sv = config['serving']
    assert sv['kv_pages'] == sv['slots'] * (
        config['n_positions'] // sv['page_tokens']) + 1
    pool = sv['kv_pages'] * sv['page_tokens'] * 24 * 4096
    assert round(pool / 1e9, 2) == 4.83
    assert round((costs.weight_bytes(config) + pool) / 1e9, 1) == 14.2


def test_the_accepted_cost_functions_read_this_file_as_it_stands(config):
    assert costs_axk1.layers(config) == (0, 24)
    assert costs_axk1.expert_params(config) == costs.expert_params(config)
    assert config['n_routed_experts'] == config['num_experts'] == 16
    assert config['router_experts'] == 128


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'paged_block_attention': 0.3, 'moe_experts': 1.1, 'mul': 0.3}
    programs = {'decode': {'calls': 150, 'device_s': 3.0},
                'prefill': {'calls': 20, 'device_s': 0.6}}
    counters = {'block_passes': 50_000, 'block_commits': 10_000,
                'block_tokens': 40_000, 'block_rows': 200_000,
                'block_masked_rows': 100_000,
                'moe_layer_calls': 48_000, 'moe_pairs': 48_000 * 100,
                'moe_experts_touched': 48_000 * 16,
                'slice_decode_calls': 170, 'slice_live_tokens': 170 * 12_000,
                'slice_plain_decode_calls': 150,
                'slice_plain_live_tokens': 150 * 12_000,
                'slice_moe_layer_calls': 170 * 24,
                'slice_moe_pairs': 170 * 24 * 100,
                'slice_moe_experts_touched': 170 * 24 * 16,
                'slice_moe_prefill_layer_calls': 20 * 24,
                'slice_moe_prefill_pairs': 20 * 24 * 256,
                'slice_moe_prefill_experts_touched': 20 * 24 * 16}
    run = _run(config, ops, programs, counters,
               {'paged_block_attention': 170, 'moe_experts': 190})
    assert _read('block_passes_per_token.tpot', run) == 1.25
    assert _read('block_commit_share.tpot', run) == 0.2
    assert _read('block_masked_rows_share.tpot', run) == 0.5
    assert _read('block_attn_roofline.tpot', run) == pytest.approx(
        100 * (170 * 24 * 12_000 * 4096 / 819e9) / 0.3)
    need = costs.block_step_bytes(config, 12_000, 16)
    outside = 24 * (18_874_624 + 262_144 + 2 * 2048) + 18992 * 2048 + 2048
    assert need == 4 * outside + 24 * 16 * 4 * 4_718_592 \
        + 24 * 12_000 * 4096
    assert _read('sdar_block_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.02)
    # the accepted readers, on this file
    assert _read('moe_share.tpot', run) == pytest.approx(55.0)
    assert _read('moe_pairs_per_expert.tpot', run) == pytest.approx(100 / 16)
    assert _read('moe_experts_touched_share.tpot', run) == \
        pytest.approx(100.0)
    least = 190 * 24 * costs.expert_params(config) * 4 * 16 / 819e9
    assert _read('moe_gated_expert_roofline.tpot', run) == pytest.approx(
        100 * least / 1.1)


def test_new_readers_find_nothing_on_a_run_without_the_counters(config):
    """A line of a program without a block step: no such op, span or
    counter. Nothing, no raise."""
    run = _run(config, {'mul': 1.5, 'paged_attention': 0.2},
               {'decode': {'calls': 100, 'device_s': 1.6}},
               {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100,
                'slice_plain_decode_calls': 100,
                'slice_plain_live_tokens': 100, 'slice_decode_calls': 100,
                'slice_live_tokens': 100, 'slice_moe_layer_calls': 10,
                'slice_moe_experts_touched': 10})
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_entries_follow_the_older_ones_and_the_cell_is_listed_where_it_reports():
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    at = names.index(NEW[0])
    assert at == 97 and names[at:] == NEW
    for m in man['per_layer'][at:]:
        assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
        mod = manifest.layer_metric(man, m['name'])
        assert (mod.LAYER, mod.UNIT, mod.BETTER, mod.SOURCE) == \
            (m['layer'], m['unit'], m['better'], m['source'])
    cells = [w['name'] for w in man['workloads']]
    assert cells.index(CELL) == 9 and len(cells) == 10
    assert sum(w['chips'] == 4 for w in man['workloads']) == 1
    entry = man['workloads'][-1]
    assert entry['chips'] == 1 and len(entry['why']) <= 200
    assert entry['traffic'] == 'blockgen_open'
    assert man['configs'][-1]['name'] == 'sdar-30b-a3b-chat-serve'
    assert len(man['configs'][-1]['why']) <= 200
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert set(NEW) | set(ACCEPTED) <= listed
    # every metric that lists the seven serving cells lists this one,
    # but for PR 55's six whose own accepted test
    # (test_idle_account.py) holds their lists to those seven cells
    held = {'idle_in_program_share.tpot', 'idle_empty_share.tpot',
            'idle_admit_share.tpot', 'idle_dispatch_share.tpot',
            'engine_empty_share.tpot', 'admit_ms_mean.tpot'}
    assert not listed & held
    seven = {'gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason',
             'axk1_serve_docfollow', 'granite4hs_serve_sessions',
             'sthink21b_serve_mixed', 'solar2_serve_chat_shared'}
    for m in man['per_layer'] + man['end_to_end']:
        if seven <= set(m.get('workloads', ())) and m['name'] not in held:
            assert m['workloads'][-1] == CELL, m['name']
    assert not listed & {'decode_hbm_roofline.tpot', 'gdn_share.tpot',
                         'ssm_share.tpot', 'mla_share.tpot',
                         'paged_attn_kv4_roofline.tpot',
                         'sthink_decode_hbm_roofline.tpot'}


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
    assert differ == set(config['reduced']) == {
        'num_hidden_layers', 'num_experts', 'vocab_size'}
    assert config['published']['num_hidden_layers'] == 48
    assert config['published']['num_experts'] == 128
    assert config['published']['vocab_size'] == 151936 == 8 * 18992
    # the floors: four layers of the one kind, eight experts, an eighth
    assert config['num_hidden_layers'] >= 12 and config['num_experts'] >= 8
    assert config['vocab_size'] * 8 >= 151936
    assert {'block_length', 'denoising_steps', 'remasking', 'head',
            'qk_norm', 'mask_id', 'weights'} <= set(config['assumed'])
    assert {'dtype', 'transfer_clamp'} <= set(config['departures'])
    gen = config['generation']
    assert (gen['block_length'], gen['denoising_steps'], gen['remasking'],
            gen['temperature']) == (4, 4, 'low_confidence_static', 0)
    assert config['mask_token_id'] == config['vocab_size'] - 1
    assert config['trace_programs'] == {
        'decode': ['paged_block_attention'],
        'prefill': ['paged_prefill_mask']}


def test_the_traffic_file_is_what_the_cell_states():
    mix = manifest.read_json('benchmarks/traffic/blockgen_open.json')
    p = mix['params']
    assert (mix['generator'], mix['drive']) == (
        'harness.traffic:open_loop', 'harness.drives:open_loop')
    assert p['prompt_tokens'] == [64, 1024]
    assert p['output_tokens'] == [128, 512]
    assert (p['preroll_s'], p['timeout_s'], p['trace_seconds']) == (20, 60, 4)
    entry = manifest.load()['workloads'][-1]
    assert ('%g req/s' % p['rate_rps']) in entry['why']
    plan = manifest.resolve(mix['generator'])(
        p, 2**31 + 3, manifest.read_json(
            'benchmarks/configs/sdar-30b-a3b-chat-serve.json'), 45)
    assert plan['judged'] == round(p['rate_rps'] * 45)
    assert all(len(r['prompt']) + r['max_new'] <= 1536
               and max(r['prompt']) <= 18991 for r in plan['requests'])


def test_the_check_s_prompts_by_hand(config):
    from builders import sdar_moe as builder
    from reference import sdar_moe as ref
    for cfg in (config, runner._overlaid(config, config['rehearse'])):
        dims = ref.dims_of(cfg)
        sv = cfg['correct']
        prompts = builder.check_prompts(2**31 + 9, dims, sv,
                                        cfg['serving']['page_tokens'])
        by = dict(zip(builder.LANES, prompts))
        assert [len(by['mod%d' % r]) % 4 for r in range(4)] == [0, 1, 2, 3]
        assert len(by['long']) > 2 * cfg['serving']['prefill_chunk']
        assert len(by['parent']) % 16 == 8
        assert len(by['followup']) == len(by['parent']) + 6
        assert list(by['followup'][:len(by['parent'])]) == list(by['parent'])
        assert all(p.max() < dims.mask_id for p in prompts)
        # every lane's prompt, its blocks and a block more fit a slot
        assert max(map(len, prompts)) + 4 * (sv['blocks'] + 2) \
            <= cfg['n_positions']
        assert sv['filler_streams'] + len(prompts) <= cfg['serving']['slots']


def test_probe_and_spans_agree_and_the_controls_fail():
    """tools/readings_sdar.py at the `control_test` widths, one seed: the
    program correct, each of the four controls not correct; and over the
    check's passes the builder's probe counts what the program's own
    `paged.decode.tables` spans say (a base probe would name a lane-pass
    a lane step only where it was a commit: under a third of them)."""
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, 'benchmarks', 'tools',
                                      'readings_sdar.py'),
         '--workload', CELL, '--seeds', str(2**31 + 11), '--rehearse',
         '--control-test', '--probe'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=1500)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert 'program seed %d' % (2**31 + 11) in out
    assert out.count('-> correct') == 1
    for name in ('bfloat16', 'causal_in_block', 'commit_left_out',
                 'misaligned_prefix'):
        assert 'control %s: not correct in 1 of 1 seeds' % name in out
    probe = next(l for l in out.splitlines() if l.startswith('probe '))
    got = dict(kv.split('=') for kv in probe.split()[1:])
    assert got['decode_calls'] == got['span_steps'] != '0'
    assert got['live_tokens'] == got['span_live_tokens']
    assert got['lanes'] == got['span_lanes']
    assert int(got['grew_lanes']) * 3 < int(got['lanes'])
