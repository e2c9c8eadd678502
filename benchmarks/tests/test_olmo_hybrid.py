"""The hybrid configuration's yardstick on the CPU: costs_hybrid against a
hand count at the published sizes, each new reader's arithmetic on made-up
plain data, the cell's rehearsal line, and the bf16-stored control against
the limits at the rehearse widths."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import costs_hybrid, manifest, runner

CELL = 'olmohyb_serve_long'
NEW = ['gdn_share.tpot', 'gdn_step_roofline.tpot', 'gdn_chunk_roofline.tpot',
       'hybrid_decode_hbm_roofline.tpot', 'recurrent_state_mb.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


def test_published_sizes_by_hand(config):
    d, h, dk, dv, f, v = 3840, 30, 96, 192, 11008, 100352
    # q, k 11.06 M each; v, output gate, output projection 22.12 M each
    assert d * h * dk == 11_059_200 and d * h * dv == 22_118_400
    mlp = 3 * d * f + 2 * d                                   # 126.8 M
    linear = (2 * 11_059_200 + 3 * 22_118_400 + 4 * h * (2 * dk + dv)
              + 2 * d * h + 2 * h + dv + mlp)
    full = 4 * d * d + 2 * d + mlp
    assert costs_hybrid.layer_params(config, 'linear_attention') == linear
    assert costs_hybrid.layer_params(config, 'full_attention') == full
    assert int(linear / 1e5) == 2155 and int(full / 1e5) == 1858  # 215.5, 185.8 M
    assert int((3 * linear + full) / 1e6) == 832             # a period
    assert v * d == 385_351_680                              # embedding, head
    assert costs_hybrid.kinds(config) == \
        ['linear_attention'] * 3 + ['full_attention'] \
        + ['linear_attention'] * 3 + ['full_attention']
    total = 6 * linear + 2 * full + 2 * v * d + d
    assert costs_hybrid.param_count(config) == total
    assert round(costs_hybrid.weight_bytes(config) / 1e9, 2) == 9.74
    # state: 30 x 96 x 192 floats a lane a layer, 3 x 11520 convolution rows
    assert costs_hybrid.state_bytes_per_lane(config) == 2_211_840
    assert costs_hybrid.conv_bytes_per_lane(config) == 138_240
    assert round(costs_hybrid.recurrent_state_bytes(config, 32) / 1e9, 2) \
        == 0.45
    assert costs_hybrid.kv_bytes_per_token(config) == 61_440


def test_step_and_chunk_costs_by_hand(config):
    assert costs_hybrid.gdn_step_bytes(config, 20) == 20 * 2 * 2_211_840
    # a token, a head, blocks of 64: 2 x 64 x 96 + 64 x 288 + 64 x 192
    # + 6 x 96 x 192
    per = 12_288 + 18_432 + 12_288 + 110_592
    assert costs_hybrid.gdn_chunk_flops(config, 256) == 256 * 30 * per
    assert costs_hybrid.gdn_chunk_bytes(config, 256) == \
        4 * 256 * 30 * (2 * 96 + 2 * 192) + 2 * 2_211_840
    # a decode step: weights without the embedding, K/V, state both ways
    assert costs_hybrid.decode_step_bytes(config, 1000, 10) == \
        costs_hybrid.weight_bytes(config) - 4 * 100352 * 3840 \
        + 1000 * 61_440 + 2 * 10 * 6 * (2_211_840 + 138_240)


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'gated_delta_step': 0.06, 'gated_delta_chunk': 0.03,
           'short_conv': 0.01, 'mul': 1.5}
    programs = {'decode': {'calls': 100, 'device_s': 1.6},
                'prefill': {'calls': 20, 'device_s': 0.3}}
    counters = {'decode_calls': 1000, 'state_lanes': 20_000,
                'live_tokens': 25_000_000, 'prefill_calls': 200,
                'prefill_tokens': 40_000,
                'recurrent_state_bytes_max': 451_215_360,
                # the slice's own 100 steps (none carried a chunk too) and
                # 20 chunks, at the window's means
                'slice_decode_calls': 100, 'slice_state_lanes': 2000,
                'slice_plain_decode_calls': 100,
                'slice_plain_state_lanes': 2000,
                'slice_plain_live_tokens': 2_500_000,
                'slice_prefill_calls': 20, 'slice_state_tokens': 4000}
    run = _run(config, ops, programs, counters,
               {'gated_delta_step': 100, 'gated_delta_chunk': 20})
    assert _read('gdn_share.tpot', run) == pytest.approx(5.0)
    # 100 steps x 6 layers x 20 lanes x 2 x 2.21 MB over 819 GB/s, in 0.06 s
    assert _read('gdn_step_roofline.tpot', run) == pytest.approx(
        100 * (100 * 6 * 20 * 2 * 2_211_840 / 819e9) / 0.06)
    # 200 tokens a chunk: bytes 17.6 us against FLOPs 4.7 us: the bytes bind
    least = max(costs_hybrid.gdn_chunk_flops(config, 200) / 197e12,
                costs_hybrid.gdn_chunk_bytes(config, 200) / 819e9)
    assert least == costs_hybrid.gdn_chunk_bytes(config, 200) / 819e9
    assert _read('gdn_chunk_roofline.tpot', run) == pytest.approx(
        100 * 20 * 6 * least / 0.03)
    need = costs_hybrid.decode_step_bytes(config, 25_000, 20)
    assert _read('hybrid_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.016)
    assert _read('recurrent_state_mb.tpot', run) == pytest.approx(451.21536)


def test_readers_find_nothing_on_a_program_without_the_ops(config):
    """The parent's line: no such op, span or counter. Nothing, no raise."""
    run = _run(config, {'mul': 1.5},
               {'decode': {'calls': 100, 'device_s': 1.6}},
               {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100})
    assert [_read(n, run) for n in NEW] == [None] * 5


def test_entries_are_listed_in_order_and_list_the_cell():
    man = manifest.check(manifest.load())
    names = [m['name'] for m in man['per_layer']]
    # membership and relative order, not a place in the list: the next
    # addition may stand anywhere
    assert [n for n in names if n in NEW] == NEW
    for m in man['per_layer']:
        if m['name'] in NEW:
            # the cell first, where it reports; later cells behind it
            assert m['workloads'][0] == CELL and m['moves'] == 'tpot_p50_ms'
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert 'decode_hbm_roofline.tpot' not in listed and set(NEW) <= listed


def test_the_check_runs_at_the_window_s_occupancy(config):
    """`correct` by hand: chunks of 256 make 1, 3, 6 and 12 of the four
    prompts, a decode step falls between any two chunks of a stream
    opened later, and 8 steps of all together; 16 short streams stay
    live beside the four, so the compared steps feed 20 lanes."""
    from builders import olmo_hybrid
    sv = config['correct']
    assert olmo_hybrid.check_decoded(
        sv, config['serving']['prefill_chunk']) == \
        [2 + 5 + 11 + 8, 5 + 11 + 8, 11 + 8, 8]
    assert max(sv['prompt_tokens']) + sv['decode_tokens'] \
        <= config['n_positions']
    assert sv['filler_streams'] + len(sv['prompt_tokens']) == 20
    assert sv['filler_streams'] <= \
        config['serving']['slots'] - len(sv['prompt_tokens'])
    small = runner._overlaid(config, config['rehearse'])
    assert 0 < small['correct']['filler_streams'] <= \
        small['serving']['slots'] - len(small['correct']['prompt_tokens'])


def test_rehearsal_line_counts_the_state(config):
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, 'benchmarks', 'run.py'),
         '--workload', CELL, '--seed', str(2**31 + 26), '--seconds', '2',
         '--trace', '1', '--rehearse'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0
    small = runner._overlaid(config, config['rehearse'])
    want = costs_hybrid.recurrent_state_bytes(
        small, small['serving']['slots']) / 1e6
    assert line['metrics']['recurrent_state_mb.tpot']['value'] == \
        pytest.approx(want)
    window = next(l for l in proc.stdout.splitlines()
                  if l.startswith('window '))
    counted = dict(kv.split('=') for kv in window.split()[1:])
    assert float(counted['state_lanes']) > 0
    assert float(counted['state_resets']) > 0


def test_bf16_stored_control_reads_over_the_limits(config):
    """At the rehearse widths, 24 layers deep (the error grows with depth;
    `control_test`): the control against the reference at the same matmul
    precision and at "highest", as serve_comparisons compares."""
    import jax.numpy as jnp
    from reference import olmo_hybrid as ref
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
    assert ref.rel_l2(control, same) > limits['logits_rel_l2']
    assert ref.rel_l2(control, truth) > limits['logits_rel_l2_to_highest']
    # and the reference agrees with itself far under them
    assert ref.rel_l2(same, truth) < 0.01 * limits['logits_rel_l2']
