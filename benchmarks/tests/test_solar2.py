"""The Solar-Open2 configuration's yardstick on the CPU: costs_solar2
against a hand count at the published sizes, the traffic's design (the
same multiset under three seeds, every part the seed's one deal), each
new reader's arithmetic on made-up plain data (and nothing, without a
raise, on a run that lacks the counters), the accepted readers on this
cell's file as it stands, the file against the catalog's rules, the
check's streams, the cell's rehearsal, and the bf16-stored control at
the rehearse widths."""
import collections
import json
import os

import numpy as np
import pytest

from harness import (costs_solar2 as costs, manifest, runner,
                     traffic_sessions, traffic_shared_sys)

CELL = 'solar2_serve_chat_shared'
NEW = ['kda_share.tpot', 'kda_step_roofline.tpot', 'kda_chunk_roofline.tpot',
       'solar2_decode_hbm_roofline.tpot',
       'moe_sigmoid320_expert_roofline.tpot']
ACCEPTED = ['moe_share.tpot', 'moe_pairs_per_expert.tpot',
            'moe_experts_touched_share.tpot', 'prefix_reuse_share.tpot',
            'snapshot_adopt_share.tpot', 'state_snapshot_mb.tpot',
            'recurrent_state_mb.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


@pytest.fixture(scope='module')
def mix():
    return manifest.read_json('benchmarks/traffic/chat_shared_sys_open.json')


def test_published_sizes_by_hand(config):
    d = 4096
    # q, k, v 100.66 M; out 33.55 M; two low-rank gates 3.15 M; W_b
    # 0.26 M; convolution 0.10 M
    qkv, out = d * 24576, 8192 * d
    gates = 2 * (d * 128 + 128 * 8192)
    assert (qkv, out, gates) == (100_663_296, 33_554_432, 3_145_728)
    kda = qkv + out + gates + d * 64 + 4 * 24576 + 64 + 8192 + 128 + d
    assert costs.mixer_params(config, 'kda') == kda
    assert round(kda / 1e6, 2) == 137.74
    # q, gate and o 33.55 M each; k + v 8.39 M
    attn = 3 * d * 8192 + 2 * d * 1024 + d
    assert costs.mixer_params(config, 'full_attention') == attn
    assert round(attn / 1e6, 2) == 109.06
    # router 1.31 M and its bias, shared expert 15.73 M
    outside = d * 320 + 320 + d + 3 * d * 1280
    assert costs.expert_params(config) == 3 * d * 1280 == 15_728_640
    assert costs.sublayer_params(config, held=0) == outside
    assert round((kda + outside) / 1e6, 1) == 154.8
    assert round((attn + outside) / 1e6, 1) == 126.1
    assert round((3 * kda + attn + 4 * outside) / 1e6, 1) == 590.4
    assert costs.kinds(config) == ['full_attention', 'kda', 'kda', 'kda'] * 2
    dense = 6 * kda + 2 * attn + 8 * outside
    assert costs.param_count(config) == \
        dense + 8 * 10 * 15_728_640 + 2 * 24576 * d + d
    assert round(costs.weight_bytes(config) / 1e9, 2) == 10.56
    # the published model: 12 attention + 36 linear, 320 experts, 196608
    whole = dict(config, num_hidden_layers=48, n_routed_experts=320,
                 vocab_size=196608)
    assert round(costs.param_count(whole) / 1e9, 1) == 250.3
    assert costs.kinds(whole).count('kda') == 36
    # state: 64 x 128 x 128 floats and 3 x 24576 rows a lane a layer
    assert costs.state_bytes_per_lane(config) == 4_194_304
    assert costs.conv_bytes_per_lane(config) == 294_912
    assert costs.snapshot_row_bytes(config) == 6 * 4_489_216
    assert round(costs.snapshot_row_bytes(config) / 1e6, 1) == 26.9
    assert costs.state_copy_bytes(config) == 2 * 26_935_296
    assert costs.kv_bytes_per_token(config) == 16384
    sv = config['serving']
    assert round(costs.recurrent_state_bytes(config, sv['slots']) / 1e9, 2) \
        == 1.29
    held = costs.weight_bytes(config) \
        + (sv['slots'] + sv['snapshot_rows']) * 26_935_296 \
        + sv['kv_pages'] * sv['page_tokens'] * 16384
    assert round(held / 1e9, 1) == 13.7                  # of 16: chips_layout
    # a decode step: all of it but the embedding (a gather) and the
    # routed experts not chosen
    assert costs.decode_step_bytes(config, 76_000, 38, 9.5) == \
        4 * (dense + 24576 * d + d) + 4 * 8 * 9.5 * 15_728_640 \
        + 76_000 * 16384 + 2 * 38 * 26_935_296
    # ISSUE 52's arithmetic: 38 lanes of 2000 tokens, every expert read
    step = costs.decode_step_bytes(config, 76_000, 38, 10)
    assert round(step / 819e9 * 1e3, 1) == 16.4
    linear = costs.kda_weight_bytes(config) \
        + 6 * costs.kda_step_bytes(config, 38)
    assert round(costs.kda_weight_bytes(config) / 1e9, 1) == 3.3
    assert round(6 * costs.kda_step_bytes(config, 38) / 1e9, 1) == 1.9
    assert round(100 * linear / step) == 39
    # a chunk of 256 tokens, blocks of 64: per token and head 2 x 64 x
    # 128 + 64 x 256 + 64 x 128 + 6 x 128 x 128
    assert costs.kda_chunk_flops(config, 256) == \
        256 * 64 * (16384 + 16384 + 8192 + 98304)
    assert costs.kda_chunk_bytes(config, 256) == \
        4 * 256 * 64 * 5 * 128 + 2 * 4_194_304


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'kda_step': 0.30, 'kda_chunk': 0.08, 'short_conv': 0.02,
           'moe_experts': 0.6, 'paged_attention': 0.12, 'mul': 0.5}
    programs = {'decode': {'calls': 100, 'device_s': 1.9},
                'prefill': {'calls': 20, 'device_s': 0.5}}
    counters = {'decode_calls': 1000, 'state_lanes': 30_000,
                'moe_layer_calls': 8000, 'moe_pairs': 290_000,
                'moe_experts_touched': 78_000,
                'recurrent_state_bytes_max': 48 * 26_935_296,
                'state_snapshot_bytes_max': 8 * 26_935_296,
                'prefix_tokens_reused': 850, 'prompt_tokens_admitted': 1000,
                'streams_opened': 200, 'snapshots_adopted': 200,
                'slice_decode_calls': 100, 'slice_state_lanes': 3700,
                'slice_live_tokens': 7_400_000,
                'slice_plain_decode_calls': 100,
                'slice_plain_state_lanes': 3700,
                'slice_plain_live_tokens': 7_400_000,
                'slice_prefill_calls': 20, 'slice_state_tokens': 3000,
                'slice_moe_layer_calls': 800, 'slice_moe_pairs': 30_000,
                'slice_moe_experts_touched': 7900,
                'slice_moe_prefill_layer_calls': 160,
                'slice_moe_prefill_pairs': 6000,
                'slice_moe_prefill_experts_touched': 1590}
    run = _run(config, ops, programs, counters,
               {'kda_step': 100, 'kda_chunk': 20, 'moe_experts': 120,
                'paged_attention': 100})
    assert _read('kda_share.tpot', run) == pytest.approx(20.0)
    # 37 lanes a step in the slice (30 in the window's mean)
    assert _read('kda_step_roofline.tpot', run) == pytest.approx(
        100 * (100 * 6 * 37 * 2 * 4_194_304 / 819e9) / 0.30)
    least = max(costs.kda_chunk_bytes(config, 150) / 819e9,
                costs.kda_chunk_flops(config, 150) / 197e12)
    assert _read('kda_chunk_roofline.tpot', run) == pytest.approx(
        100 * 20 * 6 * least / 0.08)
    need = costs.decode_step_bytes(config, 74_000, 37, 7900 / 800)
    assert _read('solar2_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.019)
    ops.update({'hlo:slice-done': 0.2, 'hlo:slice-start': 0.005,
                'hlo:copy-done': 0.005})
    dec = 100 * 8 * costs.expert_bytes(config, 7900 / 800) / 819e9
    pre = 20 * 8 * max(costs.expert_bytes(config, 1590 / 160) / 819e9,
                       costs.expert_flops(config, 6000 / 160) / 197e12)
    assert costs.expert_bytes(config, 10) == 10 * 3 * 4096 * 1280 * 4
    assert _read('moe_sigmoid320_expert_roofline.tpot', run) == \
        pytest.approx(100 * (dec + pre) / 0.81)
    # the accepted readers, on this file
    assert _read('moe_share.tpot', run) == pytest.approx(30.0)
    assert _read('moe_pairs_per_expert.tpot', run) == pytest.approx(290 / 78)
    assert _read('moe_experts_touched_share.tpot', run) == pytest.approx(
        100 * 78_000 / (8000 * 10))
    assert _read('prefix_reuse_share.tpot', run) == pytest.approx(85.0)
    assert _read('snapshot_adopt_share.tpot', run) == pytest.approx(100.0)
    assert _read('state_snapshot_mb.tpot', run) == pytest.approx(215.482368)
    assert _read('recurrent_state_mb.tpot', run) == pytest.approx(1292.894208)


def test_new_readers_find_nothing_on_a_run_without_the_counters(config):
    """A line of a program without the block: no such op, span or
    counter. Nothing, no raise; on another configuration's file too."""
    counters = {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100}
    programs = {'decode': {'calls': 100, 'device_s': 1.6}}
    run = _run(config, {'mul': 1.5}, programs, counters)
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)
    other = manifest.read_json('benchmarks/configs/olmo-hybrid-7b-serve.json')
    run = _run(other, {'mul': 1.5, 'moe_experts': 0.2}, programs,
               dict(counters, slice_moe_layer_calls=8))
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_entries_follow_the_older_ones_and_the_cell_is_listed_where_it_reports():
    """This PR's entries stand in order behind those the benchmark had
    (82 per-layer metrics, 8 cells, 7 configurations)."""
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    at = names.index(NEW[0])
    assert at >= 82 and names[at:at + len(NEW)] == NEW
    for m in man['per_layer'][at:at + len(NEW)]:
        assert m['workloads'] == [CELL] and m['moves'] == 'tpot_p50_ms'
    cells = [w['name'] for w in man['workloads']]
    assert cells.index(CELL) >= 8
    assert man['workloads'][cells.index(CELL)]['chips'] == 1
    assert sum(w['chips'] == 4 for w in man['workloads']) == 1
    cfg_at = [c['name'] for c in man['configs']].index(
        'solar-open2-250b-serve')
    assert cfg_at >= 7
    # the driver refuses a line over 200 characters before any run
    for entry in (man['workloads'][cells.index(CELL)], man['configs'][cfg_at]):
        assert all(len(v) <= 200 and v.isprintable()
                   for v in entry.values() if isinstance(v, str)), entry
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert set(NEW) | set(ACCEPTED) <= listed
    # every metric that lists the six older serving cells lists this one
    six = {'gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason',
           'axk1_serve_docfollow', 'granite4hs_serve_sessions',
           'sthink21b_serve_mixed'}
    for m in man['per_layer'] + man['end_to_end']:
        if six <= set(m.get('workloads', ())):
            assert CELL in m['workloads'], m['name']
    # readers tied to another configuration's costs, or to ops this
    # block does not run, are not listed
    assert not listed & {'decode_hbm_roofline.tpot', 'gdn_share.tpot',
                         'gdn_step_roofline.tpot', 'ssm_share.tpot',
                         'moe_gated_expert_roofline.tpot',
                         'moe_gated_expert_fetch_roofline.tpot',
                         'paged_attn_kv8_roofline.tpot',
                         'state_copy_roofline.tpot',
                         'granite_decode_hbm_roofline.tpot'}


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
        'num_hidden_layers', 'n_routed_experts', 'vocab_size'}
    for key in differ:
        assert config['published'][key] == row['config'][key]
    assert config['router_experts'] == row['config']['n_routed_experts']
    # the floors: whole periods, 8 experts, an eighth of the vocabulary
    kinds = costs.kinds(config)
    assert kinds.count('full_attention') == 2 and kinds.count('kda') == 6
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['config']['vocab_size']
    man = manifest.load()
    entry = next(c for c in man['configs'] if c['file'].endswith(
        'solar-open2-250b-serve.json'))
    assert entry['reduced'] == config['reduced']
    assert entry['source'] == config['source'] == row['source_url']


# -- the traffic ----------------------------------------------------------------

def _plan(mix, config, seed, seconds=45.0):
    return traffic_shared_sys.chat_shared_sys(mix['params'], seed, config,
                                              seconds)


def _multiset(requests):
    return collections.Counter(
        (r['system'], len(r['prompt']), r['max_new']) for r in requests)


def test_the_design_is_the_same_multiset_under_three_seeds(mix, config):
    rate = mix['params']['rate_rps']
    n = int(rate * 45 + 1e-9)       # 121: the whole requests that fit
    plans = [_plan(mix, config, s) for s in (7, 2**31 + 9, 12345)]
    system = traffic_sessions.system_prompts(mix['params'], config)
    assert [len(s) for s in system] == [1024, 1360, 1712, 2048]
    design = traffic_shared_sys.design(mix['params'], n)
    want = collections.Counter(
        (s, len(system[s]) + u, o) for s, u, o in design)
    for plan in plans:
        assert plan['judged'] == n and len(plan['requests']) == 2 * n
        window = plan['requests'][:n]
        tail = plan['requests'][n:]
        assert _multiset(window) == _multiset(tail) == want
        assert all(0 <= r['due'] < 45 for r in window)
        assert all(45 <= r['due'] < 90 for r in tail)
        assert all(0 <= r['due'] < 20 for r in plan['preroll'])
        # every request opens on its system prompt, then ids of its own
        for r in window[:20]:
            k = len(system[r['system']])
            assert (r['prompt'][:k] == system[r['system']]).all()
            assert 32 <= len(r['prompt']) - k <= 512
            assert 256 <= r['max_new'] <= 1024
        assert max(len(r['prompt']) + r['max_new'] for r in window) \
            <= 2048 + 512 + 1024 == config['n_positions']
        assert all((a == b).all() for a, b in zip(plan['warm'], system))
        # both parts replay the seed's one deal: the tail is the window
        # 45 s later, with token ids of its own
        assert np.allclose([r['due'] + 45 for r in window],
                           [r['due'] for r in tail])
        assert [r['max_new'] for r in window] == [r['max_new'] for r in tail]
        assert not any((a['prompt'][-8:] == b['prompt'][-8:]).all()
                       for a, b in zip(window, tail))
        # the pre-roll is the end of the same deal, one part earlier
        last = [r for r in window if r['due'] >= 25]
        assert [r['max_new'] for r in plan['preroll']] == \
            [r['max_new'] for r in last]
    # the seed decides the order and where the bursts fall
    assert [r['due'] for r in plans[0]['requests']] != \
        [r['due'] for r in plans[1]['requests']]
    shares = collections.Counter(s for s, _, _ in design)
    assert max(shares.values()) - min(shares.values()) <= 1
    gaps = np.diff([r['due'] for r in plans[0]['requests'][:n]])
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.05)
    assert gaps.std() == pytest.approx(1 / rate, rel=0.15)    # exponential


def test_the_same_seed_gives_the_same_plan(mix, config):
    one, again = _plan(mix, config, 2**31 + 5), _plan(mix, config, 2**31 + 5)
    for key in ('requests', 'preroll'):
        assert [r['due'] for r in one[key]] == [r['due'] for r in again[key]]
        assert all((x['prompt'] == y['prompt']).all()
                   for x, y in zip(one[key], again[key]))


def test_the_check_s_streams_by_hand(config, mix):
    from builders import solar_open2 as builder
    from reference import solar_open2 as ref
    sv = config['correct']
    dims = ref.dims_of(config)
    system = traffic_sessions.system_prompts(mix['params'], config)
    streams = builder.check_streams(3, dims, sv, system)
    assert [i for i, _ in streams] == [0, 1, 2, 3]
    for (i, prompt), n in zip(streams, sv['message_tokens']):
        assert (prompt[:len(system[i])] == system[i]).all()
        assert len(prompt) == len(system[i]) + n
        assert n % 16 and n % 256
    # two of them take more than one chunk: steps run between chunks
    assert builder.check_decoded(sv, 256) == [
        1 + 1 + sv['decode_tokens'], 1 + 1 + sv['decode_tokens'],
        1 + sv['decode_tokens'], sv['decode_tokens']]
    assert max(len(p) for _, p in streams) + 2 + sv['decode_tokens'] \
        <= config['n_positions']
    assert sv['filler_streams'] + len(streams) <= config['serving']['slots']
    # the occupancy the window runs at: over 30 lanes of 48
    assert sv['filler_streams'] + len(streams) > 30
    small = runner._overlaid(config, config['rehearse'])
    assert small['correct']['filler_streams'] + 4 <= \
        small['serving']['slots']


def test_the_cell_rehearses_and_lacks_only_the_trace_s_metrics():
    from test_cells import _run as run_cell
    line, lines = run_cell('--workload', CELL, '--seed', str(2**31 + 7),
                           '--seconds', '3', '--trace', '1', '--rehearse')
    assert line['correct'] is True and line['failed'] == 0
    sources = {m['name']: m['source'] for m in manifest.load()['per_layer']}
    lacks = [n.strip(',') for l in lines if l.startswith('the result line '
             'lacks ') for n in l.split(', which')[0].split()[4:]]
    assert lacks and all(sources[n] == 'device_trace' for n in lacks), lacks
    assert {'state_snapshot_mb.tpot', 'snapshot_adopt_share.tpot',
            'prefix_reuse_share.tpot', 'recurrent_state_mb.tpot'} \
        <= set(line['metrics'])
    # requests adopt their system prompt's snapshot (all of them: the
    # check's line below; a window of seconds counts an opening and its
    # adoption on different sides of its edges)
    assert 0 < line['metrics']['snapshot_adopt_share.tpot']['value'] <= 100
    opened = next(l for l in lines if l.startswith('check: streams opened'))
    assert 'on [32, 64, 32, 64]' in opened


def test_bf16_stored_control_reads_over_the_limits(config):
    """At the rehearse widths with wider weights (`control_test`): the
    control against the reference at "highest", as serve_comparisons
    compares."""
    import jax.numpy as jnp
    from reference import solar_open2 as ref
    small = runner._overlaid(config, config['rehearse'])
    small = dict(small, **config['control_test'])
    dims = ref.dims_of(small)
    assert dims.layers == 8 and dims.kinds[:2] == ('full_attention', 'kda')
    key = ref.seed_key(2**31 + 7)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, dims.vocab, size=128), jnp.int32)
    rows = slice(100, 109)
    truth, same, control = (
        ref.logits(key, dims, toks, p, rows)
        for p in ('float32', 'float32_default', 'bfloat16'))
    limits = config['correct']
    print('control', ref.rel_l2(control, truth), ref.rel_l2(same, truth))
    # by the limit that separates on the chip: the rows against the
    # reference at the program's own matmul precision (on the CPU that
    # arithmetic is the truth's)
    assert ref.rel_l2(control, same) > limits['logits_rel_l2']
    # and by the steady number of the decode rows (PR 53): the row in the
    # middle, through the builder's own comparison; the reference in the
    # program's place reads a hundredth of the limit there
    from builders import solar_open2 as b
    as_lanes = lambda x: [np.asarray(x)]
    by_name = lambda checks: {c['name']: c for c in checks}
    bad = by_name(b.serve_comparisons(*map(as_lanes, (control, truth, same)),
                                      limits))
    assert 'decode_logits_rel_l2' not in bad
    assert bad['decode_rows_rel_l2_median']['limit'] == \
        limits['decode_rows_rel_l2_median'] < limits['logits_rel_l2']
    assert bad['decode_rows_rel_l2_median']['value'] > \
        limits['decode_rows_rel_l2_median']
    good = by_name(b.serve_comparisons(*map(as_lanes, (same, truth, same)),
                                       limits))
    assert all(c['value'] <= 0.01 * c['limit'] for c in good.values())
    # and the reference agrees with itself far under them
    assert ref.rel_l2(same, truth) < 0.01 * limits['logits_rel_l2']


def test_one_turned_lane_does_not_move_the_decode_rows_median(config):
    """What PR 53's refused seed showed: one lane of four wrong by 0.085
    in all its rows and the others by 0.025. The worst lane's number
    read 0.085; the median row reads a sound lane's, and the control's
    rows, all alike at 0.11, read over the limit."""
    from builders import solar_open2 as b
    rng = np.random.default_rng(3)
    limits = config['correct']
    same = [rng.standard_normal((n, 64)).astype(np.float32)
            for n in (27, 27, 26, 25)]

    def off_by(lanes, shares):
        out = []
        for lane, share in zip(lanes, shares):
            noise = rng.standard_normal(lane.shape).astype(np.float32)
            noise *= share * np.linalg.norm(lane, axis=-1, keepdims=True) \
                / np.linalg.norm(noise, axis=-1, keepdims=True)
            out.append(lane + noise)
        return out

    def median(got):
        checks = b.serve_comparisons(got, same, same, limits)
        return next(c for c in checks
                    if c['name'] == 'decode_rows_rel_l2_median')

    turned = median(off_by(same, (0.025, 0.025, 0.085, 0.025)))
    assert turned['value'] == pytest.approx(0.025, rel=1e-3)
    assert turned['value'] < turned['limit'] == 0.065
    control = median(off_by(same, (0.11, 0.11, 0.11, 0.11)))
    assert control['value'] == pytest.approx(0.11, rel=1e-3)
    assert control['value'] > control['limit']
