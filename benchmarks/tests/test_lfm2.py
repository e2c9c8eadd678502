"""The LFM2 configuration's yardstick on the CPU: costs_lfm2 against a
hand count at the published sizes (2458.3 M held, 8.340 B published,
224 KB a page), each new reader's arithmetic on made-up plain data (and
nothing, without a raise, on a run that lacks the counters), the
accepted readers on this cell's file as it stands, the file against the
catalog's rules, the entries' places, the traffic file and what the
builder takes of its plan, the check's sessions, and the bf16-stored
control at the `control_test` widths: it has to come out as not
correct."""
import json
import os

import numpy as np
import pytest

from harness import costs_axk1, costs_lfm2 as costs, manifest, runner, \
    traffic_sessions, traffic_sessions_replay

CELL = 'lfm2_serve_agentloop'
NEW = ['paged_attn_d64_roofline.tpot', 'conv_share.tpot',
       'lfm2_decode_hbm_roofline.tpot', 'conv_pool_mb.tpot',
       'conv_page_adopt_share.tpot', 'prefix_offprompt_share.tpot']
ACCEPTED = ['moe_share.tpot', 'moe_pairs_per_expert.tpot',
            'moe_experts_touched_share.tpot',
            'moe_gated_expert_roofline.tpot', 'prefix_reuse_share.tpot']
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


@pytest.fixture(scope='module')
def traffic():
    return manifest.read_json('benchmarks/traffic/agent_loops_open.json')


def test_published_sizes_by_hand(config):
    d = 2048
    # conv mixer: in 2048 x 6144 = 12.58 M, out 2048 x 2048 = 4.19 M, 3
    # taps a channel, the norm's gain
    assert 3 * d * d == 12_582_912 and d * d == 4_194_304
    assert costs.mixer_params(config, 'conv') == \
        12_582_912 + 4_194_304 + 3 * d + d == 16_785_408
    # attention mixer: q, o 2048 x 2048 each, k + v 2 x 2048 x 512, two
    # gains of 64, the norm's gain
    assert costs.mixer_params(config, 'full_attention') == \
        2 * 4_194_304 + 2 * d * 8 * 64 + 128 + d == 10_487_936
    assert costs.expert_params(config) == 3 * d * 1792 == 11_010_048
    assert 32 * 11_010_048 == 352_321_536
    assert costs.ff_params(config, 0) == 3 * d * 7168 + d == 44_042_240
    assert costs.ff_params(config, 2) == \
        352_321_536 + d * 32 + 32 + d == 352_389_152
    assert costs.kinds(config) == ['conv', 'conv', 'full_attention', 'conv',
                                   'conv', 'conv', 'full_attention', 'conv']
    held = (6 * 16_785_408 + 2 * 10_487_936 + 2 * 44_042_240
            + 6 * 352_389_152 + 65536 * d + d)
    assert costs.param_count(config) == held == 2_458_327_488
    assert round(held / 1e6, 1) == 2458.3
    assert round(costs.weight_bytes(config) / 1e9, 2) == 9.83
    assert round(6 * 352_321_536 / 1e6, 1) == 2113.9
    # the published model: 24 layers, 18 conv + 6 attention, 2 dense
    whole = dict(config, num_hidden_layers=24)
    assert costs.kinds(whole).count('conv') == 18
    assert round(costs.param_count(whole) / 1e9, 3) == 8.340
    assert round(costs.weight_bytes(whole) / 1e9, 1) == 33.4
    # ten layers would leave no cache
    assert round(costs.weight_bytes(dict(config, num_hidden_layers=10))
                 / 1e9, 2) == 12.79
    # a page: K/V 8 KB a token over 2 attention layers, 16 KB of conv
    # rows in each of 6 conv layers
    assert costs.kv_bytes_per_token(config) == 2 * 2 * 8 * 64 * 4 == 8192
    assert costs.conv_rows_bytes(config) == 2 * d * 4 == 16_384
    assert costs.page_bytes(config, 16) == 16 * 8192 + 6 * 16_384 \
        == 224 * 1024
    sv = config['serving']
    pool = sv['kv_pages'] * costs.page_bytes(config, sv['page_tokens'])
    # ISSUE 60's 12288 pages (2.82 GB, 12.65 with the weights) filled
    # inside the window; 19456 do not (PERF.md section 6, PR 60)
    assert round(12288 * costs.page_bytes(config, 16) / 1e9, 2) == 2.82
    assert sv['kv_pages'] == 19456 and round(pool / 1e9, 2) == 4.46
    assert round((costs.weight_bytes(config) + pool) / 1e9, 2) == 14.30
    assert sv['snapshot_rows'] == 0


def test_the_file_holds_the_catalog_row(config):
    if not os.path.exists(CATALOG):
        pytest.skip('no catalog here')
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'LFM2-8B-A1B')
    assert config['source'] == row['source_url']
    changed = [k for k, v in row['config'].items() if config.get(k) != v]
    assert changed == config['reduced'] == ['num_hidden_layers']
    assert config['published']['num_hidden_layers'] == \
        row['config']['num_hidden_layers'] == 24
    entry = next(c for c in manifest.load()['configs']
                 if c['name'] == config['name'])
    assert entry['reduced'] == config['reduced']
    assert entry['source'] == config['source']


def test_the_accepted_cost_functions_read_this_file_as_it_stands(config):
    assert costs_axk1.layers(config) == (2, 6)
    assert costs_axk1.expert_params(config) == costs.expert_params(config)
    assert config['n_routed_experts'] == config['num_experts'] == 32
    assert config['first_k_dense_replace'] == config['num_dense_layers'] == 2


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'paged_attention': 0.1, 'short_conv': 0.04, 'moe_experts': 1.5}
    programs = {'decode': {'calls': 150, 'device_s': 2.1},
                'prefill': {'calls': 20, 'device_s': 0.4}}
    counters = {'slice_decode_calls': 170, 'slice_live_tokens': 170 * 200_000,
                'slice_plain_decode_calls': 150,
                'slice_plain_live_tokens': 150 * 200_000,
                'slice_plain_lanes': 150 * 48,
                'slice_moe_layer_calls': 170 * 6,
                'slice_moe_pairs': 170 * 6 * 192,
                'slice_moe_experts_touched': 170 * 6 * 32,
                'slice_moe_prefill_layer_calls': 20 * 6,
                'slice_moe_prefill_pairs': 20 * 6 * 1024,
                'slice_moe_prefill_experts_touched': 20 * 6 * 32,
                'moe_layer_calls': 18_000, 'moe_pairs': 18_000 * 192,
                'moe_experts_touched': 18_000 * 32,
                'page_state_bytes_max': 6000 * 6 * 16_384,
                'page_state_streams_adopted': 90, 'streams_opened': 100,
                'prefix_tokens_reused': 400_000,
                'prefix_offprompt_tokens': 100_000,
                'prompt_tokens_admitted': 500_000}
    run = _run(config, ops, programs, counters,
               {'paged_attention': 170, 'moe_experts': 190})
    assert _read('paged_attn_d64_roofline.tpot', run) == pytest.approx(
        100 * (170 * 200_000 * 8192 / 819e9) / 0.1)
    assert _read('conv_share.tpot', run) == pytest.approx(2.0)
    need = costs.decode_step_bytes(config, 200_000, 48, 32)
    outside = 2_458_327_488 - 6 * 352_321_536
    assert need == 4 * (outside + 6 * 32 * 11_010_048) \
        + 200_000 * 8192 + 2 * 48 * 6 * 16_384
    assert _read('lfm2_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.014)
    assert _read('conv_pool_mb.tpot', run) == pytest.approx(589.824)
    assert _read('conv_page_adopt_share.tpot', run) == pytest.approx(90.0)
    assert _read('prefix_offprompt_share.tpot', run) == pytest.approx(25.0)
    # the accepted readers, on this file
    assert _read('moe_share.tpot', run) == pytest.approx(75.0)
    assert _read('moe_pairs_per_expert.tpot', run) == pytest.approx(6.0)
    assert _read('moe_experts_touched_share.tpot', run) == \
        pytest.approx(100.0)
    assert _read('prefix_reuse_share.tpot', run) == pytest.approx(80.0)
    least = 190 * 6 * 32 * 4 * 11_010_048 / 819e9
    assert _read('moe_gated_expert_roofline.tpot', run) == pytest.approx(
        100 * least / 1.5, rel=0.02)


def test_readers_find_nothing_where_the_program_has_nothing(config):
    """The parent's program, or another cell's: no span, no counter, no
    raise."""
    run = _run(config, {'mul': 1.0}, {}, {})
    for name in NEW:
        assert _read(name, run) is None


def test_the_entries_stand_at_the_end_of_their_lists():
    man = manifest.check(manifest.load())
    assert man['configs'][-1]['name'] == 'lfm2-8b-a1b-serve'
    assert man['workloads'][-1] == {
        'name': CELL, 'config': 'lfm2-8b-a1b-serve',
        'traffic': 'agent_loops_open', 'chips': 1,
        'why': man['workloads'][-1]['why']}
    assert len(man['workloads'][-1]['why']) <= 200
    assert [m['name'] for m in man['per_layer'][-len(NEW):]] == NEW
    for m in man['per_layer'][-len(NEW):]:
        assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
        reader = manifest.layer_metric(man, m['name'])
        assert (reader.UNIT, reader.BETTER, reader.SOURCE, reader.LAYER) == \
            (m['unit'], m['better'], m['source'], m['layer'])
    listed = {m['name'] for m in man['per_layer']
              if CELL in m.get('workloads', ())}
    assert set(ACCEPTED) <= listed
    for m in man['per_layer']:
        if CELL in m.get('workloads', ()):
            assert m['workloads'][-1] == CELL
    e2e = {m['name']: m for m in man['end_to_end']}
    assert e2e['tpot_p50_ms']['workloads'][-1] == CELL


def test_the_traffic_is_a_file_of_parameters(config, traffic):
    assert traffic['generator'] == \
        'harness.traffic_sessions_replay:replayed_sessions'
    assert traffic['drive'] == 'harness.drives:open_loop'
    p = traffic['params']
    assert (p['n_system_prompts'], p['system_tokens'], p['turns']) == \
        (8, [1536, 3072], [6, 8, 10])
    assert (p['user_tokens'], p['answer_tokens']) == ([32, 384], [32, 192])
    assert (p['think_s'], p['answer_s_per_token']) == ([0.3, 1.5], 0)
    assert (p['timeout_s'], p['preroll_s'], p['trace_seconds']) == \
        (60, 20, 4)
    # the longest session is the context served
    assert p['system_tokens'][1] + max(p['turns']) * (
        p['user_tokens'][1] + p['answer_tokens'][1]) == \
        config['n_positions'] == 8832
    assert config['n_positions'] // config['serving']['page_tokens'] == 552
    for prompt in traffic_sessions.system_prompts(p, config):
        assert len(prompt) % 16 == 0 and 1536 <= len(prompt) <= 3072


def test_a_window_holds_one_design_of_turns_and_no_system_prompt_alone(
        config, traffic):
    """The plan at a reduced rate (a CPU test's seconds): the same turns
    in the window at the same times under every seed, with other words
    in them, and the builder's warm list (the plan's without its leading
    system prompts) holds conversations in progress only, each longer
    than the system prompt it opens with."""
    params = dict(traffic['params'], rate_rps=2.0)
    n_sys = params['n_system_prompts']
    system = traffic_sessions.system_prompts(params, config)
    judged, plans = set(), []
    for seed in (1, 2**31 + 7):
        plan = traffic_sessions_replay.replayed_sessions(
            params, seed, config, 45.0)
        plans.append(plan)
        judged.add(plan['judged'])
        # the window holds chat_sessions' design, turn for turn
        window = plan['requests'][:plan['judged']]
        design = traffic_sessions.design(params, 45.0)
        assert sorted(r['max_new'] for r in window) == \
            sorted(design['answer'])
        for a, b in zip(plan['warm'][:n_sys], system):
            assert np.array_equal(a, b)
        for prompt in plan['warm'][n_sys:]:
            assert any(len(prompt) > len(s)
                       and np.array_equal(prompt[:len(s)], s)
                       for s in system)
        assert max(len(r['prompt']) + r['max_new']
                   for r in plan['requests']) <= config['n_positions']
    assert len(judged) == 1 and judged.pop() > 60
    # one deal: the two seeds' turns are due at the same times with the
    # same lengths, share a system prompt and nothing behind it, and a
    # turn still begins with its session's last prompt
    for a, b in zip(*(p['requests'] for p in plans)):
        assert (a['due'], a['max_new'], len(a['prompt'])) == \
            (b['due'], b['max_new'], len(b['prompt']))
        head = len(system[a['system']])
        assert np.array_equal(a['prompt'][:head], b['prompt'][:head])
        assert np.mean(a['prompt'][head:] == b['prompt'][head:]) < 0.01
    last = {}
    for r in plans[0]['requests']:
        if r['session'] in last:
            before = last[r['session']]
            assert np.array_equal(r['prompt'][:len(before)], before)
        last[r['session']] = r['prompt']
    assert [len(p) for p in plans[0]['warm']] == \
        [len(p) for p in plans[1]['warm']]


def test_the_check_opens_on_the_four_kinds_of_boundary(config):
    from builders import lfm2 as b
    from reference import lfm2 as ref
    dims = ref.dims_of(config)
    sv = config['correct']
    pt = config['serving']['page_tokens']
    s = b.check_sessions(5, dims, sv, pt)
    assert list(s) == list(b.SESSIONS)
    assert s['tail']['opens_at'] % pt == pt // 2
    assert s['forked']['opens_at'] % pt == pt // 2
    assert s['foreign_system']['opens_at'] % pt == 0
    assert len(s['foreign_system']['earlier']) == \
        s['foreign_system']['opens_at'] + sv['foreign_message_tokens']
    assert not np.array_equal(
        s['foreign_system']['earlier'][s['foreign_system']['opens_at']:][:pt],
        s['foreign_system']['last'][s['foreign_system']['opens_at']:][:pt])
    assert s['cold']['earlier'] is None and s['cold']['opens_at'] == 0
    for name in ('tail', 'forked'):
        assert np.array_equal(s[name]['earlier'],
                              s[name]['last'][:s[name]['opens_at']])
    longest = max(len(v['last']) for v in s.values())
    decoded = b.check_decoded(s, sv, config['serving']['prefill_chunk'])
    assert longest + max(decoded) + 1 <= config['n_positions']
    assert longest > 0.95 * config['n_positions'] - 300
    # the step's occupancy is the window's: compared, owner and fillers
    assert 40 <= len(b.SESSIONS) + 1 + sv['filler_streams'] \
        <= config['serving']['slots']


def test_the_control_is_not_correct(config):
    """The bf16-stored reference in the program's place at the rehearse
    widths, limits as committed: it must miss one on every seed, and the
    float32 reference itself passes with room."""
    from builders import gpt2, lfm2 as b
    from reference import lfm2 as ref
    from tools import readings_lfm2
    small = runner._overlaid(config, config['rehearse'])
    small = runner._overlaid(small, config['control_test'])
    dims = ref.dims_of(small)
    for seed in (1, 2**31 + 3):
        checks = readings_lfm2.control(small, dims, seed)
        assert any(c['value'] > c['limit'] for c in checks), checks
        sv, serving = small['correct'], small['serving']
        sessions = b.check_sessions(seed, dims, sv, serving['page_tokens'])
        n = b.check_decoded(sessions, sv, serving['prefill_chunk'])
        lanes = [list(sessions[k]['last']) + [1] * m
                 for k, m in zip(b.SESSIONS, n)]
        refs = b.serve_reference(seed, dims, lanes, n)
        sound = gpt2.serve_comparisons([t for t, _ in refs],
                                       [t for t, _ in refs],
                                       [s_ for _, s_ in refs], sv)
        assert all(c['value'] * 10 <= c['limit'] for c in sound), sound
