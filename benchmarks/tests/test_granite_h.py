"""The Granite 4.0-H Small configuration's yardstick on the CPU:
costs_granite_h against a hand count at the published sizes, the session
generator's design (nesting, the fixed multiset, the seed's part), each
new reader's arithmetic on made-up plain data (and nothing, without a
raise, on a run that lacks the counters), the accepted readers on this
cell's file as it stands, the file against the catalog's rules, the
check's sessions, and the bf16-stored control at the rehearse widths."""
import collections
import json
import os

import numpy as np
import pytest

from harness import (costs_axk1, costs_granite_h as costs, costs_nemotron_h,
                     manifest, runner, traffic_sessions)

CELL = 'granite4hs_serve_sessions'
NEW = ['snapshot_adopt_share.tpot', 'state_snapshot_mb.tpot',
       'state_copy_roofline.tpot', 'granite_decode_hbm_roofline.tpot',
       'paged_attn_kv8_roofline.tpot',
       'moe_gated_expert_fetch_roofline.tpot']
ACCEPTED = ['ssm_share.tpot', 'ssm_step_roofline.tpot',
            'ssm_chunk_roofline.tpot', 'ssm_state_mb.tpot', 'moe_share.tpot',
            'moe_pairs_per_expert.tpot', 'moe_experts_touched_share.tpot',
            'prefix_reuse_share.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


@pytest.fixture(scope='module')
def mix():
    return manifest.read_json('benchmarks/traffic/chat_sessions_open.json')


def test_published_sizes_by_hand(config):
    d = 4096
    # in-projection 4096 x 16768 = 68.68 M, out-projection 8192 x 4096
    mamba = d * 16768 + 5 * 8448 + 3 * 128 + 8192 + 8192 * d + d
    assert d * 16768 == 68_681_728 and 8192 * d == 33_554_432
    assert costs.mixer_params(config, 'mamba') == mamba
    assert round(mamba / 1e6, 2) == 102.29
    # q, o 16.78 M each; k + v 8.39 M
    attn = 2 * d * 32 * 128 + 2 * d * 8 * 128 + d
    assert costs.mixer_params(config, 'attention') == attn
    assert round(attn / 1e6, 2) == 41.95
    # router 0.29 M, shared expert 3 x 4096 x 1536 = 18.87 M
    outside = d * 72 + 3 * d * 1536 + d
    assert costs.expert_params(config) == 3 * d * 768 == 9_437_184
    assert costs.sublayer_params(config, held=0) == outside
    assert round((mamba + outside) / 1e6, 2) == 121.46
    assert round((attn + outside) / 1e6, 2) == 61.12
    assert costs.kinds(config) == ['mamba'] * 5 + ['attention'] \
        + ['mamba'] * 4
    dense = 9 * mamba + attn + 10 * outside
    assert round(dense / 1e6, 1) == 1154.3
    assert costs.param_count(config) == \
        dense + 10 * 9 * 9_437_184 + 12544 * d + d
    assert round(costs.weight_bytes(config) / 1e9, 2) == 8.22
    # the published model: 36 mamba + 4 attention, 72 experts, 100352 rows
    whole = dict(config, num_hidden_layers=40, num_local_experts=72,
                 vocab_size=100352)
    assert round(costs.param_count(whole) / 1e9, 1) == 32.2
    # state: 128 x 64 x 128 floats and 3 x 8448 rows a lane a layer
    assert costs.state_bytes_per_lane(config) == 4_194_304 + 101_376
    assert costs.snapshot_row_bytes(config) == 9 * 4_295_680
    assert round(costs.snapshot_row_bytes(config) / 1e6, 2) == 38.66
    assert costs.state_copy_bytes(config) == 2 * 38_661_120
    assert costs.kv_bytes_per_token(config) == 8192
    sv = config['serving']
    held = costs.weight_bytes(config) \
        + (sv['slots'] + sv['snapshot_rows']) * 38_661_120 \
        + sv['kv_pages'] * sv['page_tokens'] * 8192
    assert 13.5 < held / 1e9 < 14.6                      # of 16: chips_layout
    # a decode step: all of it but the routed experts not chosen
    assert costs.decode_step_bytes(config, 90_000, 30, 8.5) == \
        4 * (dense + 12544 * d + d) + 4 * 10 * 8.5 * 9_437_184 \
        + 90_000 * 8192 + 2 * 30 * 38_661_120


def test_the_accepted_cost_functions_read_this_file_as_it_stands(config):
    """The keys the file repeats under the names the accepted cost
    functions read give the published sizes."""
    assert costs_nemotron_h.kinds(config) == list('MMMMM*MMMM')
    assert config['hybrid_override_pattern'] == ''.join(
        'M' if k == 'mamba' else '*' for k in config['layer_types'])
    assert costs_nemotron_h.state_bytes_per_lane(config) == 4_194_304
    assert costs_nemotron_h.ssd_step_bytes(config, 30) == 30 * 2 * 4_194_304
    # a token, blocks of 256: C B^T 256 x 128 x 1 group, its product with
    # dt x 256 x 64 x 128, C h and B^T (dt x) 4 x 128 x 64 x 128
    assert costs_nemotron_h.ssd_chunk_flops(config, 256) == \
        256 * (256 * 128 + 256 * 64 * 128 + 4 * 128 * 64 * 128)
    assert costs_nemotron_h.ssd_chunk_bytes(config, 256) == \
        4 * 256 * (2 * 8192 + 2 * 128) + 2 * 4_194_304
    assert costs_nemotron_h.paged_attention_bytes(config, 90_000) == \
        90_000 * 8192
    assert costs_axk1.layers(config) == (0, 10)
    assert costs_axk1.expert_params(config) == costs.expert_params(config)
    assert config['n_routed_experts'] == config['num_local_experts'] == 9


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'ssd_step': 0.45, 'ssd_chunk': 0.04, 'short_conv': 0.01,
           'moe_experts': 0.6, 'paged_attention': 0.12, 'mul': 0.5,
           'state_row_copy': 0.0025}
    programs = {'decode': {'calls': 100, 'device_s': 1.9},
                'prefill': {'calls': 20, 'device_s': 0.5},
                'state_copy': {'calls': 16, 'device_s': 0.0026}}
    counters = {'decode_calls': 1000, 'state_lanes': 30_000,
                'live_tokens': 90_000_000, 'prefill_calls': 200,
                'prefill_tokens': 40_000, 'moe_layer_calls': 10_000,
                'moe_pairs': 400_000, 'moe_experts_touched': 88_000,
                'moe_prefill_layer_calls': 2000,
                'moe_prefill_pairs': 500_000,
                'moe_prefill_experts_touched': 18_000,
                'ssm_state_bytes_max': 48 * 38_661_120,
                'state_snapshot_bytes_max': 64 * 38_661_120,
                'prefix_tokens_reused': 850, 'prompt_tokens_admitted': 1000,
                'streams_opened': 200, 'snapshots_adopted': 190,
                'slice_decode_calls': 100, 'slice_state_lanes': 3100,
                'slice_live_tokens': 9_200_000,
                'slice_plain_decode_calls': 100,
                'slice_plain_state_lanes': 3100,
                'slice_plain_live_tokens': 9_200_000,
                'slice_prefill_calls': 20, 'slice_state_tokens': 4000,
                'slice_moe_layer_calls': 1000, 'slice_moe_pairs': 41_000,
                'slice_moe_experts_touched': 8900,
                'slice_moe_prefill_layer_calls': 200,
                'slice_moe_prefill_pairs': 52_000,
                'slice_moe_prefill_experts_touched': 1800}
    run = _run(config, ops, programs, counters,
               {'ssd_step': 100, 'ssd_chunk': 20, 'moe_experts': 120,
                'paged_attention': 100, 'state_row_copy': 16})
    assert _read('snapshot_adopt_share.tpot', run) == pytest.approx(95.0)
    assert _read('state_snapshot_mb.tpot', run) == pytest.approx(2474.31168)
    assert _read('state_copy_roofline.tpot', run) == pytest.approx(
        100 * (16 * 2 * 38_661_120 / 819e9) / 0.0025)
    # lanes, tokens and experts of the slice's own steps, not the
    # window's mean (8.8 experts a layer there, 8.9 in the slice)
    need = costs.decode_step_bytes(config, 92_000, 31, 8.9)
    assert _read('granite_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.019)
    # the accepted readers, on this file
    assert _read('ssm_share.tpot', run) == pytest.approx(25.0)
    assert _read('moe_share.tpot', run) == pytest.approx(30.0)
    # since PR 53 they too take the slice's own steps: 31 lanes, not 30
    assert _read('ssm_step_roofline.tpot', run) == pytest.approx(
        100 * (100 * 9 * 31 * 2 * 4_194_304 / 819e9) / 0.45)
    least = max(costs_nemotron_h.ssd_chunk_bytes(config, 200) / 819e9,
                costs_nemotron_h.ssd_chunk_flops(config, 200) / 197e12)
    assert _read('ssm_chunk_roofline.tpot', run) == pytest.approx(
        100 * 20 * 9 * least / 0.04)
    # the slice's own steps, not the window's mean: 92 000 tokens a step
    assert _read('paged_attn_kv8_roofline.tpot', run) == pytest.approx(
        100 * (100 * 92_000 * 8192 / 819e9) / 0.12)
    dec = 100 * 10 * costs.expert_params(config) * 4 * 8.9 / 819e9
    pre = 20 * 10 * max(costs.expert_params(config) * 4 * 9 / 819e9,
                        2 * 260 * costs.expert_params(config) / 197e12)
    assert _read('moe_gated_expert_roofline.tpot', run) == pytest.approx(
        100 * (dec + pre) / 0.6)
    # the same op over its own time and its weights' sliced fetches,
    # from the slice's own counts
    ops.update({'hlo:slice-done': 0.2, 'hlo:slice-start': 0.005,
                'hlo:copy-done': 0.005})
    dec = 100 * 10 * costs.expert_bytes(config, 8.9) / 819e9
    pre = 20 * 10 * max(costs.expert_bytes(config, 9) / 819e9,
                        costs.expert_flops(config, 260) / 197e12)
    assert costs.expert_bytes(config, 9) == 9 * 3 * 4096 * 768 * 4
    assert costs.expert_flops(config, 260) == 2 * 260 * 3 * 4096 * 768
    assert _read('moe_gated_expert_fetch_roofline.tpot', run) == \
        pytest.approx(100 * (dec + pre) / 0.81)
    assert _read('moe_pairs_per_expert.tpot', run) == pytest.approx(400 / 88)
    assert _read('moe_experts_touched_share.tpot', run) == pytest.approx(
        100 * 8.8 / 9)
    assert _read('ssm_state_mb.tpot', run) == pytest.approx(1855.73376)
    assert _read('prefix_reuse_share.tpot', run) == pytest.approx(85.0)


def test_new_readers_find_nothing_on_a_run_without_the_counters(config):
    """A line of a program without snapshot rows: no such op, span or
    counter. Nothing, no raise."""
    run = _run(config, {'mul': 1.5},
               {'decode': {'calls': 100, 'device_s': 1.6}},
               {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100})
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_entries_follow_the_older_ones_and_the_cell_is_listed_where_it_reports():
    """This PR's entries stand in order behind those the benchmark had
    (69 per-layer metrics, 6 cells, 5 configurations). Not "last": the
    next PR appends behind them, and a test that pins the end fails on
    the first addition (tests/test_axk1.py's does, since this PR)."""
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    at = names.index(NEW[0])
    assert at >= 69 and names[at:at + len(NEW)] == NEW
    for m in man['per_layer'][at:at + len(NEW)]:
        # the cell first, where it reports; later cells behind it
        assert m['workloads'][0] == CELL and m['moves'] == 'tpot_p50_ms'
    cells = [w['name'] for w in man['workloads']]
    assert cells.index(CELL) >= 6
    assert man['workloads'][cells.index(CELL)]['chips'] == 1
    assert [c['name'] for c in man['configs']].index(
        'granite-4.0-h-small-serve') >= 5
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert set(NEW) | set(ACCEPTED) <= listed
    # every metric that lists the four serving cells lists this one
    four = {'gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason',
            'axk1_serve_docfollow'}
    for m in man['per_layer'] + man['end_to_end']:
        if four <= set(m.get('workloads', ())):
            assert CELL in m['workloads'], m['name']
    # moe_gated_expert_roofline.tpot and paged_attn_gqa_roofline.tpot
    # compute on this file (above) and are not listed: the first takes
    # the op's time without the sliced fetches of its weights and read
    # 109 %, the second the window's mean tokens and read 126 %; each
    # has a reader of this cell's own (PERF.md sections 6 and 7, PR 45)
    assert not listed & {'decode_hbm_roofline.tpot', 'gdn_share.tpot',
                         'nemo_decode_hbm_roofline.tpot',
                         'moe_expert_roofline.tpot', 'mla_share.tpot',
                         'paged_attn_gqa_roofline.tpot',
                         'moe_gated_expert_roofline.tpot'}


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
        'num_hidden_layers', 'num_local_experts', 'vocab_size'}
    for key in differ:
        assert config['published'][key] == row['config'][key]
    assert config['router_experts'] == row['config']['num_local_experts']
    # the floors: a whole period, 8 experts, an eighth of the vocabulary
    kinds = costs.kinds(config)
    assert kinds.count('attention') == 1 and kinds.count('mamba') == 9
    assert config['num_local_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['config']['vocab_size']


# -- the sessions ---------------------------------------------------------------

def _plan(mix, config, seed, seconds=45.0):
    return traffic_sessions.chat_sessions(mix['params'], seed, config,
                                          seconds)


def test_a_turn_s_prompt_nests_its_session_s_earlier_turns(mix, config):
    plan = _plan(mix, config, 7)
    system = traffic_sessions.system_prompts(mix['params'], config)
    assert [len(s) for s in system] == [1024, 1360, 1712, 2048]
    assert all(len(s) % 16 == 0 for s in system)
    by_session = collections.defaultdict(list)
    for r in plan['preroll']:
        by_session[r['session']].append(r)
    for r in plan['requests']:          # `due` counted from the pre-roll
        by_session[r['session']].append(dict(r, due=r['due'] + 20.0))
    nested = 0
    for turns in by_session.values():
        turns.sort(key=lambda r: r['turn'])
        first = turns[0]
        assert (first['prompt'][:len(system[first['system']])]
                == system[first['system']]).all()
        for a, b in zip(turns, turns[1:]):
            if b['turn'] != a['turn'] + 1:
                continue
            # the earlier prompt, a scripted answer as long as was asked
            # for, and a new message of 32-256 tokens
            grew = len(b['prompt']) - len(a['prompt']) - a['max_new']
            assert (b['prompt'][:len(a['prompt'])] == a['prompt']).all()
            assert 32 <= grew <= 256
            assert 2.0 + 0.03 * a['max_new'] - 1e-9 <= b['due'] - a['due'] \
                <= 5.0 + 0.03 * a['max_new'] + 1e-9
            nested += 1
    assert nested > 100
    lo, hi = mix['params']['answer_tokens']
    assert all(lo <= r['max_new'] <= hi for r in plan['requests'])
    assert max(len(r['prompt']) + r['max_new'] for r in plan['requests']) \
        <= 2048 + 5 * 512 <= config['n_positions']
    assert all(r['prompt'].max() < config['vocab_size']
               for r in plan['requests'])


def test_the_design_is_one_for_every_seed_and_the_seed_deals_it(mix, config):
    a, b = (traffic_sessions.design(mix['params'], 45.0) for _ in range(2))
    rate = mix['params']['rate_rps']
    # parts of the window's length, one of them the window (from the
    # pre-roll's end), enough of them before it for the longest session
    assert a['t0'] == [-70.0, -25.0, 20.0, 65.0]
    assert a['t0'][0] <= -traffic_sessions.span_s(mix['params'])
    assert a['sessions'] == b['sessions']
    assert all((a[k] == b[k]).all()
               for k in ('gaps', 'user', 'answer', 'think'))
    shapes = collections.Counter(a['sessions'])
    assert set(shapes) == {(s, t) for s in range(4) for t in (3, 4, 5)}
    assert max(shapes.values()) - min(shapes.values()) <= 1
    # session starts at exponential gaps (the law's quantiles: their
    # deviation is their mean), rate_rps / 4 of them a second
    n = len(a['sessions'])
    assert n == round(rate / 4 * 45)
    assert a['gaps'].sum() == pytest.approx(45.0)
    assert a['gaps'].std() == pytest.approx(45.0 / n, rel=0.1)
    turns = sum(t for _, t in a['sessions'])
    assert turns / 45.0 == pytest.approx(rate, rel=0.02)
    assert len(a['user']) == len(a['think']) == turns
    assert 32 <= a['user'][0] <= 33 and 254 <= a['user'][-1] <= 256
    assert 64 <= a['answer'][0] <= 65 and 254 <= a['answer'][-1] <= 256
    assert 2.0 < a['think'][0] < a['think'][-1] < 5.0
    one, two, again = (_plan(mix, config, s) for s in (7, 2**31 + 9, 7))
    for key in ('requests', 'preroll'):
        assert [r['due'] for r in one[key]] == [r['due'] for r in again[key]]
        assert all((x['prompt'] == y['prompt']).all()
                   for x, y in zip(one[key], again[key]))
    assert [r['due'] for r in one['requests']] != \
        [r['due'] for r in two['requests']]
    # every part replays the seed's one deal, so what reaches into the
    # window from the part before is what leaves it for the tail: the
    # window holds the design's turns and lengths under every seed, and
    # the seed decides where its bursts fall
    plans = [one, two] + [_plan(mix, config, s) for s in range(1, 11)]
    assert {p['judged'] for p in plans} == {turns}
    for plan in plans[:3]:
        window = plan['requests'][:plan['judged']]
        tail = plan['requests'][plan['judged']:]
        assert sorted(r['max_new'] for r in window) == sorted(a['answer'])
        def rows(turns, shift):
            return np.array([(r['due'] + shift, r['turn'], r['max_new'],
                              len(r['prompt'])) for r in turns])
        assert np.allclose(rows(window, 45.0), rows(tail, 0.0), atol=1e-9)
        # token ids are a part's own: no turn of the tail finds the
        # window's pages
        assert not any((x['prompt'][-8:] == y['prompt'][-8:]).all()
                       for x, y in zip(window, tail))
    busiest = [max(np.histogram([r['due'] for r in p['requests']],
                                bins=45, range=(0, 45))[0]) for p in plans]
    assert max(busiest) >= 2 * rate        # bursts: twice the mean a second
    # each kind of turn in its steady share from the first second on
    for plan in (one, two):
        assert all(0 <= r['due'] for r in plan['requests'])
        turns = collections.Counter(
            r['turn'] for r in plan['requests'][:plan['judged']])
        assert turns[0] == round(rate / 4 * 45)    # the window is a part
        assert turns[4] > 0
        early = [r for r in plan['requests'] if r['due'] < 5]
        assert any(r['turn'] >= 2 for r in early)
        assert all(0 <= r['due'] < 20 for r in plan['preroll'])


def test_set_up_caches_what_the_first_turns_of_the_plan_reopen_on(mix,
                                                                  config):
    """Every turn of the pre-roll or the window that is not a session's
    first finds its earlier turn among the plan's own turns or among
    the prompts set-up prefills."""
    plan = _plan(mix, config, 11)
    system = traffic_sessions.system_prompts(mix['params'], config)
    assert all((a == b).all() for a, b in zip(plan['warm'], system))
    cached = {p.tobytes() for p in plan['warm']}
    for r in plan['preroll'] + plan['requests']:
        if r['turn']:
            grew = [n for n in range(32 + 64, 256 + 256 + 1)
                    if r['prompt'][:len(r['prompt']) - n].tobytes() in cached]
            assert grew, (r['session'], r['turn'])
        cached.add(r['prompt'].tobytes())


def test_the_check_s_sessions_by_hand(config):
    from builders import granite_h as builder
    from reference import granite_h as ref
    sv = config['correct']
    dims = ref.dims_of(config)
    sessions = builder.check_sessions(3, dims, sv, 16)
    lengths = [len(b) for _, b in sessions]
    assert [abs(n - want) < 16 for n, want in
            zip(lengths, sv['session_tokens'])] == [True] * 4
    assert not any(n % 256 == 0 or n % 16 == 0 for n in lengths)
    firsts = [len(a) for a, _ in sessions]
    assert firsts[sv['mid_page']] % 16 == 8
    assert not any(n % 256 == 0 for n in firsts)
    assert all((b[:len(a)] == a).all() for a, b in sessions)
    assert max(lengths) + sv['decode_tokens'] <= config['n_positions']
    assert sv['filler_streams'] <= config['serving']['slots'] - 4
    # four earlier turns, four last turns and the fillers all keep a
    # snapshot while the check runs
    assert sv['filler_streams'] + 8 <= config['serving']['snapshot_rows']
    small = runner._overlaid(config, config['rehearse'])
    assert small['correct']['filler_streams'] + 8 <= \
        small['serving']['snapshot_rows']


def test_bf16_stored_control_reads_over_the_limits(config):
    """At the rehearse widths, two periods deep (`control_test`): the
    control against the reference at "highest", as serve_comparisons
    compares."""
    import jax.numpy as jnp
    from reference import granite_h as ref
    small = runner._overlaid(config, config['rehearse'])
    small = dict(small, **config['control_test'])
    small['layer_types'] = config['layer_types']
    dims = ref.dims_of(small)
    assert dims.layers == 20
    key = ref.seed_key(2**31 + 7)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, dims.vocab, size=128), jnp.int32)
    rows = slice(100, 109)
    truth, same, control = (
        ref.logits(key, dims, toks, p, rows)
        for p in ('float32', 'float32_default', 'bfloat16'))
    limits = config['correct']
    assert ref.rel_l2(control, truth) > limits['logits_rel_l2_to_highest']
    # and the reference agrees with itself far under them
    assert ref.rel_l2(same, truth) < 0.01 * limits['logits_rel_l2']
