"""The Nemotron-H configuration's yardstick on the CPU: costs_nemotron_h
against a hand count at the published sizes, each new reader's arithmetic
on made-up plain data (and nothing, without a raise, on a program that
lacks the ops), the file against the catalog's rules, the cell's
rehearsal line, and the bf16-stored control at the rehearse widths."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import costs_nemotron_h as costs, manifest, runner

CELL = 'nemo3s_serve_reason'
NEW = ['ssm_share.tpot', 'ssm_step_roofline.tpot', 'ssm_chunk_roofline.tpot',
       'moe_share.tpot', 'moe_expert_roofline.tpot',
       'paged_attn_gqa_roofline.tpot', 'nemo_decode_hbm_roofline.tpot',
       'moe_pairs_per_expert.tpot', 'moe_experts_touched_share.tpot',
       'ssm_state_mb.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


def test_published_sizes_by_hand(config):
    d = 4096
    # in-projection 4096 x 18560 = 76.0 M, out-projection 8192 x 4096
    mamba = d * 18560 + 5 * 10240 + 3 * 128 + 8192 + 8192 * d + d
    assert d * 18560 == 76_021_760 and 8192 * d == 33_554_432
    assert costs.layer_params(config, 'M') == mamba
    assert int(mamba / 1e5) == 1096                              # 109.6 M
    # q, o 16.8 M each; k + v 2.1 M
    attn = 2 * d * 32 * 128 + 2 * d * 2 * 128 + d
    assert costs.layer_params(config, '*') == attn
    assert int(attn / 1e5) == 356                                # 35.7 M
    # router 2.1 M, latent down and up 4.2 M each, shared expert 44.0 M
    outside = d * 512 + 512 + 2 * d * 1024 + 2 * d * 5376 + d
    assert int(outside / 1e5) == 545                             # 54.5 M
    assert costs.expert_params(config) == 2 * 1024 * 2688 == 5_505_024
    assert costs.layer_params(config, 'E') == outside + 64 * 5_505_024
    assert costs.kinds(config) == list('MEMEMEM*EME')
    total = 5 * mamba + 5 * (outside + 64 * 5_505_024) + attn \
        + 2 * 16384 * d + d
    assert costs.param_count(config) == total
    assert round(costs.weight_bytes(config) / 1e9, 2) == 11.01
    # state: 128 x 64 x 128 floats a lane a layer, 3 x 10240 rows
    assert costs.state_bytes_per_lane(config) == 4_194_304
    assert costs.conv_bytes_per_lane(config) == 122_880
    assert round(costs.ssm_state_bytes(config, 64) / 1e9, 2) == 1.38
    assert costs.kv_bytes_per_token(config) == 2048
    sv = config['serving']
    pool = sv['kv_pages'] * sv['page_tokens'] * 2048
    held = costs.weight_bytes(config) + pool + costs.ssm_state_bytes(
        config, sv['slots'])
    assert round(held / 1e9, 1) == 12.7                   # of 16: chips_layout


def test_kernel_costs_by_hand(config):
    assert costs.ssd_step_bytes(config, 45) == 45 * 2 * 4_194_304
    assert costs.ssd_step_flops(config, 45) == 45 * 4 * 128 * 64 * 128
    # a token, blocks of 128: C B^T 128 x 128 x 8, its product with dt x
    # 128 x 64 x 128, C h and B^T (dt x) 4 x 128 x 64 x 128
    per = 131_072 + 1_048_576 + 4_194_304
    assert costs.ssd_chunk_flops(config, 256) == 256 * per
    assert costs.ssd_chunk_bytes(config, 256) == \
        4 * 256 * (2 * 8192 + 2 * 1024) + 2 * 4_194_304
    assert costs.expert_bytes(config, 60) == 60 * 4 * 5_505_024
    assert costs.expert_flops(config, 124) == 124 * 2 * 5_505_024
    assert costs.paged_attention_bytes(config, 36_000) == 36_000 * 2048
    # a decode step: weights without the embedding and the routed
    # experts, 5 layers of the experts touched, K/V, state both ways
    dense = costs.param_count(config) - 16384 * 4096 - 5 * 64 * 5_505_024
    assert costs.decode_step_bytes(config, 36_000, 45, 60) == \
        4 * dense + 5 * 60 * 4 * 5_505_024 + 36_000 * 2048 \
        + 2 * 45 * 5 * (4_194_304 + 122_880)


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'ssd_step': 0.30, 'ssd_chunk': 0.02, 'short_conv': 0.08,
           'moe_experts': 0.9, 'paged_attention': 0.01, 'mul': 0.5}
    programs = {'decode': {'calls': 100, 'device_s': 1.8},
                'prefill': {'calls': 20, 'device_s': 0.2}}
    counters = {'decode_calls': 1000, 'state_lanes': 45_000,
                'live_tokens': 36_000_000, 'prefill_calls': 200,
                'prefill_tokens': 40_000, 'moe_layer_calls': 5000,
                'moe_pairs': 620_000, 'moe_experts_touched': 300_000,
                'moe_prefill_layer_calls': 1000,
                'moe_prefill_pairs': 550_000,
                'moe_prefill_experts_touched': 64_000,
                'ssm_state_bytes_max': 1_381_498_880,
                # the slice's own 100 steps (none carried a chunk too) and
                # 20 chunks, at the window's means
                'slice_decode_calls': 100, 'slice_state_lanes': 4500,
                'slice_live_tokens': 3_600_000,
                'slice_plain_decode_calls': 100,
                'slice_plain_state_lanes': 4500,
                'slice_plain_live_tokens': 3_600_000,
                'slice_prefill_calls': 20, 'slice_state_tokens': 4000,
                'slice_moe_layer_calls': 500, 'slice_moe_pairs': 62_000,
                'slice_moe_experts_touched': 30_000,
                'slice_moe_prefill_layer_calls': 100,
                'slice_moe_prefill_pairs': 55_000,
                'slice_moe_prefill_experts_touched': 6400}
    run = _run(config, ops, programs, counters,
               {'ssd_step': 100, 'ssd_chunk': 20, 'moe_experts': 120,
                'paged_attention': 100})
    assert _read('ssm_share.tpot', run) == pytest.approx(20.0)
    assert _read('moe_share.tpot', run) == pytest.approx(45.0)
    # 100 steps x 5 layers x 45 lanes x 2 x 4.19 MB over 819 GB/s, in 0.3 s
    assert _read('ssm_step_roofline.tpot', run) == pytest.approx(
        100 * (100 * 5 * 45 * 2 * 4_194_304 / 819e9) / 0.30)
    # 200 tokens a chunk: FLOPs 5.5 us against bytes 25 us: the bytes bind
    least = costs.ssd_chunk_bytes(config, 200) / 819e9
    assert least > costs.ssd_chunk_flops(config, 200) / 197e12
    assert _read('ssm_chunk_roofline.tpot', run) == pytest.approx(
        100 * 20 * 5 * least / 0.02)
    # decode: 60 experts a layer; prefill: 64 experts and 550 pairs
    dec = 100 * 5 * costs.expert_bytes(config, 60) / 819e9
    pre = 20 * 5 * max(costs.expert_bytes(config, 64) / 819e9,
                       costs.expert_flops(config, 550) / 197e12)
    assert _read('moe_expert_roofline.tpot', run) == pytest.approx(
        100 * (dec + pre) / 0.9)
    assert _read('paged_attn_gqa_roofline.tpot', run) == pytest.approx(
        100 * (100 * 36_000 * 2048 / 819e9) / 0.01)
    need = costs.decode_step_bytes(config, 36_000, 45, 60)
    assert _read('nemo_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.018)
    assert _read('moe_pairs_per_expert.tpot', run) == pytest.approx(
        620 / 300)
    assert _read('moe_experts_touched_share.tpot', run) == pytest.approx(
        100 * 60 / 64)
    assert _read('ssm_state_mb.tpot', run) == pytest.approx(1381.49888)


def test_readers_find_nothing_on_a_program_without_the_ops(config):
    """The parent's line: no such op, span or counter. Nothing, no raise."""
    run = _run(config, {'mul': 1.5},
               {'decode': {'calls': 100, 'device_s': 1.6}},
               {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100})
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_entries_are_listed_in_order_and_list_the_cell():
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    assert [n for n in names if n in NEW] == NEW
    for m in man['per_layer']:
        if m['name'] in NEW:
            # the cell first, where it reports; later cells behind it
            assert m['workloads'][0] == CELL and m['moves'] == 'tpot_p50_ms'
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert set(NEW) <= listed
    assert not listed & {'decode_hbm_roofline.tpot', 'gdn_share.tpot',
                         'hybrid_decode_hbm_roofline.tpot'}
    assert CELL in next(m for m in man['end_to_end']
                        if m['name'] == 'tpot_p50_ms')['workloads']


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
    assert config['published']['n_routed_experts'] == \
        row['config']['n_routed_experts'] == config['router_experts']
    # the floors: a whole period, 8 experts, an eighth of the vocabulary
    assert sorted(costs.kinds(config)) == sorted('M' * 5 + 'E' * 5 + '*')
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['config']['vocab_size']


def test_the_check_runs_at_the_window_s_occupancy(config):
    """`correct` by hand: chunks of 256 make 1, 2, 4 and 8 of the four
    prompts, a decode step falls between any two chunks of a stream
    opened later, and 24 steps of all together; 40 short streams stay
    live beside the four, so the compared steps feed 44 lanes: the
    lanes a step has in the window."""
    from builders import olmo_hybrid
    sv = config['correct']
    assert olmo_hybrid.check_decoded(
        sv, config['serving']['prefill_chunk']) == \
        [1 + 3 + 7 + 24, 3 + 7 + 24, 7 + 24, 24]
    assert max(sv['prompt_tokens']) + sv['decode_tokens'] \
        <= config['n_positions']
    assert not any(n % 128 == 0 for n in sv['prompt_tokens'])
    assert sv['filler_streams'] + len(sv['prompt_tokens']) == 44
    assert sv['filler_streams'] <= \
        config['serving']['slots'] - len(sv['prompt_tokens'])
    small = runner._overlaid(config, config['rehearse'])
    assert 0 < small['correct']['filler_streams'] <= \
        small['serving']['slots'] - len(small['correct']['prompt_tokens'])


def test_rehearsal_line_counts_the_state_and_the_experts(config):
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, 'benchmarks', 'run.py'),
         '--workload', CELL, '--seed', str(2**31 + 36), '--seconds', '2',
         '--trace', '1', '--rehearse'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0
    small = runner._overlaid(config, config['rehearse'])
    want = costs.ssm_state_bytes(small, small['serving']['slots']) / 1e6
    got = line['metrics']
    assert got['ssm_state_mb.tpot']['value'] == pytest.approx(want)
    assert 0 < got['moe_experts_touched_share.tpot']['value'] <= 100
    assert got['moe_pairs_per_expert.tpot']['value'] >= 1
    window = next(l for l in proc.stdout.splitlines()
                  if l.startswith('window '))
    counted = dict(kv.split('=') for kv in window.split()[1:])
    assert float(counted['state_lanes']) > 0
    assert float(counted['state_resets']) > 0
    assert float(counted['moe_pairs']) > 0
    assert float(counted['moe_pairs_dropped']) == 0
    assert float(counted['moe_prefill_pairs_dropped']) == 0


def test_bf16_stored_control_reads_over_the_limits(config):
    """At the rehearse widths, two periods deep (`control_test`): the
    control against the reference at the same matmul precision and at
    "highest", as serve_comparisons compares."""
    import jax.numpy as jnp
    from reference import nemotron_h as ref
    small = runner._overlaid(config, config['rehearse'])
    small = dict(small, **config['control_test'])
    dims = ref.dims_of(small)
    key = ref.seed_key(2**31 + 7)
    toks = jnp.asarray(np.random.default_rng(5).integers(
        1, dims.vocab, size=128), jnp.int32)
    rows = slice(100, 109)
    truth, same, control = (
        ref.logits(key, dims, toks, p, rows)
        for p in ('float32', 'float32_default', 'bfloat16'))
    limits = config['correct']
    # not correct by the limit that separates on the chip (the other one
    # leaves room for a one-row prefill comparison: the file's `why`)
    assert ref.rel_l2(control, truth) > limits['logits_rel_l2_to_highest']
    # and the reference agrees with itself far under them
    assert ref.rel_l2(same, truth) < 0.01 * limits['logits_rel_l2']
