"""The A.X-K1 configuration's yardstick on the CPU: costs_axk1 against a
hand count at the published sizes, each new reader's arithmetic on
made-up plain data (and nothing, without a raise, on a program that
lacks the ops), the file against the catalog's rules, the traffic's
fixed design, the cell's rehearsal line, and the bf16-stored control at
the rehearse widths."""
import collections
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from harness import costs_axk1 as costs, manifest, runner, traffic_docs

CELL = 'axk1_serve_docfollow'
NEW = ['mla_share.tpot', 'mla_decode_roofline.tpot',
       'mla_prefill_roofline.tpot', 'moe_gated_expert_roofline.tpot',
       'axk1_decode_hbm_roofline.tpot', 'latent_cache_mb.tpot',
       'prefix_reuse_share.tpot']


@pytest.fixture(scope='module')
def config():
    man = manifest.check(manifest.load())
    return manifest.read_json(manifest.cell(man, CELL)[1]['file'])


@pytest.fixture(scope='module')
def traffic():
    man = manifest.load()
    return manifest.read_json(manifest.traffic_file(
        man, manifest.cell(man, CELL)[0]['traffic']))


def test_published_sizes_by_hand(config):
    d = 7168
    # W_DQ 11.01 M, W_UQ 18.87 M, W_DKV 4.13 M, W_UKV 8.39 M, W_O 58.72 M
    parts = [d * 1536, 1536 * 64 * 192, d * 576, 512 * 64 * 256,
             64 * 128 * d]
    assert [round(p / 1e6, 2) for p in parts] == [11.01, 18.87, 4.13, 8.39,
                                                  58.72]
    attn = sum(parts) + 1536 + 512
    assert costs.attention_params(config) == attn
    assert round(attn / 1e6, 1) == 101.1
    assert costs.expert_params(config) == 3 * d * 2048 == 44_040_192
    router = d * 192 + 192
    assert round(router / 1e6, 2) == 1.38
    outside = attn + router + 44_040_192 + 2 * d
    assert int(outside / 1e5) == 1465                           # 146.5 M
    assert costs.layer_params(config, 'experts') == outside + 8 * 44_040_192
    dense = attn + 3 * d * 18432 + 2 * d
    assert costs.layer_params(config, 'dense') == dense
    assert round(dense / 1e6, 1) == 497.5
    assert costs.layers(config) == (1, 4)
    total = dense + 4 * (outside + 8 * 44_040_192) + 2 * 20480 * d + d
    assert costs.param_count(config) == total
    assert round((total - 32 * 44_040_192) / 1e9, 3) == 1.377
    assert round(32 * 44_040_192 / 1e9, 3) == 1.409
    assert round(costs.weight_bytes(config) / 1e9, 2) == 11.15
    # a token's row: 576 values as needed, 640 as stored
    assert costs.latent_row_bytes(config) == 2304
    assert costs.stored_row_bytes(config) == 2560
    sv = config['serving']
    pool = costs.latent_cache_bytes(config, sv['kv_pages'], sv['page_tokens'])
    assert pool == sv['kv_pages'] * 16 * 2560 * 5
    held = costs.weight_bytes(config) + pool                # chips_layout
    assert round(held / 1e9, 1) == 14.5 and held < 15.0e9


def test_kernel_costs_by_hand(config):
    assert costs.mla_decode_bytes(config, 2_160_000) == 2_160_000 * 2304
    # a row and a head: a score over 576, a sum over 512
    assert costs.mla_decode_flops(config, 1000) == 1000 * 64 * 1088 * 2
    assert costs.mla_prefill_flops(config, 150, 12_000) == \
        2 * 150 * 64 * 12_000 * 1088
    assert costs.mla_prefill_bytes(config, 12_000) == 12_000 * 2304
    assert costs.expert_bytes(config, 6) == 6 * 4 * 44_040_192
    assert costs.expert_flops(config, 12) == 12 * 2 * 44_040_192
    outside = costs.param_count(config) - 20480 * 7168 - 32 * 44_040_192
    assert costs.decode_step_bytes(config, 2_160_000, 6) == \
        4 * outside + 4 * 6 * 4 * 44_040_192 + 2_160_000 * 2304


def _read(name, run):
    return manifest.layer_metric(manifest.load(), name).read(run)


def _run(config, ops, programs, counters, plan=None, op_runs=None):
    return {'config': config, 'device': {'kind': 'TPU v5 lite'},
            'counters': counters, 'plan': plan,
            'trace': {'busy_s': 2.0, 'ops': ops, 'programs': programs,
                      'op_runs': op_runs or {}}}


def test_readers_on_plain_data(config):
    ops = {'paged_latent_attention': 0.6, 'paged_latent_prefill': 0.1,
           'moe_experts': 0.5, 'mul': 0.7}
    programs = {'decode': {'calls': 100, 'device_s': 1.8},
                'prefill': {'calls': 10, 'device_s': 0.2}}
    counters = {'decode_calls': 1500, 'prefill_calls': 110,
                'prefill_tokens': 16_500, 'moe_layer_calls': 6000,
                'moe_pairs': 72_000, 'moe_experts_touched': 37_800,
                'moe_prefill_layer_calls': 440, 'moe_prefill_pairs': 22_000,
                'moe_prefill_experts_touched': 3520,
                # the slice's own 100 steps (none carried a chunk too) and
                # 10 chunks of 150 live rows, at the window's means
                'slice_decode_calls': 100, 'slice_latent_rows': 216_000_000,
                'slice_plain_decode_calls': 100,
                'slice_plain_latent_rows': 216_000_000,
                'slice_prefill_calls': 10, 'slice_prefill_tokens': 1500,
                'slice_moe_layer_calls': 400, 'slice_moe_pairs': 4800,
                'slice_moe_experts_touched': 2520,
                'slice_moe_prefill_layer_calls': 40,
                'slice_moe_prefill_pairs': 2000,
                'slice_moe_prefill_experts_touched': 320,
                'latent_cache_bytes_max': 3_000_000_000,
                'prefix_tokens_reused': 1_300_000,
                'prompt_tokens_admitted': 1_316_500}
    plan = {'judged': 2, 'requests': [{'prompt': np.zeros(10_000)},
                                      {'prompt': np.zeros(14_000)},
                                      {'prompt': np.zeros(9)}]}
    run = _run(config, ops, programs, counters, plan,
               {'paged_latent_attention': 100, 'paged_latent_prefill': 10,
                'moe_experts': 110})
    assert _read('mla_share.tpot', run) == pytest.approx(35.0)
    # 100 steps of 2.16 M rows (over 5 layers) of 2304 B, in 0.6 s
    assert _read('mla_decode_roofline.tpot', run) == pytest.approx(
        100 * (100 * 2_160_000 * 2304 / 819e9) / 0.6)
    # 150 live rows a chunk behind 12 k: the FLOPs bind
    least = costs.mla_prefill_flops(config, 150, 12_000) / 197e12
    assert least > costs.mla_prefill_bytes(config, 12_000) / 819e9
    assert _read('mla_prefill_roofline.tpot', run) == pytest.approx(
        100 * 10 * 5 * least / 0.1)
    dec = 100 * 4 * costs.expert_bytes(config, 6.3) / 819e9
    pre = 10 * 4 * max(costs.expert_bytes(config, 8) / 819e9,
                       costs.expert_flops(config, 50) / 197e12)
    assert _read('moe_gated_expert_roofline.tpot', run) == pytest.approx(
        100 * (dec + pre) / 0.5)
    need = costs.decode_step_bytes(config, 2_160_000, 6.3)
    assert _read('axk1_decode_hbm_roofline.tpot', run) == pytest.approx(
        100 * (need / 819e9) / 0.018)
    assert _read('latent_cache_mb.tpot', run) == pytest.approx(3000.0)
    assert _read('prefix_reuse_share.tpot', run) == pytest.approx(
        100 * 1_300_000 / 1_316_500)
    # the accepted expert readers this cell is appended to read the same
    # counters
    assert _read('moe_share.tpot', run) == pytest.approx(25.0)
    assert _read('moe_pairs_per_expert.tpot', run) == pytest.approx(
        72_000 / 37_800)


def test_readers_find_nothing_on_a_program_without_the_ops(config):
    """The parent's line: no such op, span or counter. Nothing, no raise."""
    run = _run(config, {'mul': 1.5},
               {'decode': {'calls': 100, 'device_s': 1.6}},
               {'decode_calls': 1000, 'live_tokens': 1000,
                'prefill_calls': 10, 'prefill_tokens': 100},
               {'judged': 0, 'requests': []})
    assert [_read(n, run) for n in NEW] == [None] * len(NEW)


def test_entries_are_listed_at_the_end_and_list_the_cell():
    man = manifest.check(manifest.load())
    # in order, together, behind the older ones (62 per-layer metrics, 5
    # cells, 4 configurations), the cell first where it reports; not
    # "last": later PRs append behind them
    names = [m['name'] for m in man['per_layer']]
    at = names.index(NEW[0])
    assert at >= 62 and names[at:at + len(NEW)] == NEW
    for m in man['per_layer'][at:at + len(NEW)]:
        assert m['workloads'][0] == CELL and m['moves'] == 'tpot_p50_ms'
    cells = [w['name'] for w in man['workloads']]
    configs = [c['name'] for c in man['configs']]
    assert cells.index(CELL) >= 5 and configs.index('axk1-serve') >= 4
    assert len(man['workloads'][cells.index(CELL)]['why']) <= 200
    assert len(man['configs'][configs.index('axk1-serve')]['why']) <= 200
    listed = {m['name'] for m in manifest.metrics_of(man, 'per_layer', CELL)}
    assert set(NEW) | {'moe_share.tpot', 'moe_pairs_per_expert.tpot',
                       'moe_experts_touched_share.tpot',
                       'chunk_gap_share.tpot'} <= listed
    # the shares that read another block's costs are not given this cell
    assert not listed & {'moe_expert_roofline.tpot',
                         'paged_attn_gqa_roofline.tpot',
                         'nemo_decode_hbm_roofline.tpot',
                         'decode_hbm_roofline.tpot', 'ssm_share.tpot'}
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
    # the floors: the dense layer and four after it, 8 experts, an
    # eighth of the vocabulary
    assert config['num_hidden_layers'] == \
        row['config']['first_k_dense_replace'] + 4
    assert config['n_routed_experts'] >= 8
    assert config['vocab_size'] * 8 >= row['config']['vocab_size']
    # 24 chips a layer: 3 to a group of 24 experts
    assert 24 * config['n_routed_experts'] == config['router_experts']
    assert config['router_experts'] // config['n_group'] \
        == 3 * config['n_routed_experts']


def test_every_seed_is_offered_the_same_triples(config, traffic):
    """The corpus comes from the mix alone; a window's requests are the
    same multiset of (document, question length, output length) in every
    seed, every document due equally often, and each fits its slot."""
    params = traffic['params']
    docs = traffic_docs.documents(params, config)
    assert len(docs) == 16
    lengths = [len(d) for d in docs]
    assert lengths[0] == 8192 and lengths[-1] == 15360
    assert all(n % 16 == 0 for n in lengths)
    assert lengths == sorted(lengths)
    assert all((a == b).all() for a, b in zip(
        docs, traffic_docs.documents(params, config)))
    seen = []
    for seed in (7, 2**31 + 5):
        plan = traffic_docs.doc_followup(params, seed, config, 45)
        n = plan['judged']
        assert n == round(params['rate_rps'] * 45)
        assert len(plan['requests']) == 2 * n
        for part in (plan['requests'][:n], plan['requests'][n:]):
            seen.append(sorted(
                (r['document'], len(r['prompt']) - lengths[r['document']],
                 r['max_new']) for r in part))
            count = collections.Counter(r['document'] for r in part)
            assert max(count.values()) - min(count.values()) <= 1
        for r in plan['requests']:
            d = r['document']
            assert (r['prompt'][:lengths[d]] == docs[d]).all()
            assert 64 <= len(r['prompt']) - lengths[d] <= 256
            assert 256 <= r['max_new'] <= 768
            assert len(r['prompt']) + r['max_new'] <= config['n_positions']
    assert seen[0] == seen[1] == seen[2] == seen[3]
    a, b = (traffic_docs.doc_followup(params, s, config, 45)['requests']
            for s in (7, 8))
    assert [r['document'] for r in a] != [r['document'] for r in b]
    # no document meets long answers only
    by_doc = collections.defaultdict(list)
    for d, _, o in seen[0]:
        by_doc[d].append(o)
    means = [np.mean(v) for v in by_doc.values()]
    assert max(means) / min(means) < 1.25


def test_the_pool_holds_the_corpus_the_lanes_and_the_check(config, traffic):
    sv, cv = config['serving'], config['correct']
    docs = traffic_docs.documents(traffic['params'], config)
    corpus = sum(len(d) for d in docs) // 16
    lanes = sv['slots'] * -(-(256 + 768) // 16)
    assert corpus + lanes < sv['kv_pages']
    # `correct`: the compared documents, the follow-up's few tokens and
    # the fillers' questions beside the cached corpus
    compared = sum(-(-(n + 256 + 300) // 16) for n in cv['document_tokens'])
    assert corpus + compared + cv['filler_streams'] * 20 < sv['kv_pages']
    assert max(cv['document_tokens']) + 256 + 300 <= config['n_positions']
    assert cv['filler_streams'] + len(cv['document_tokens']) + 1 \
        <= sv['slots']
    assert cv['followup_tokens'] < sv['page_tokens'] // 2
    small = runner._overlaid(config, config['rehearse'])
    assert small['correct']['filler_streams'] \
        + len(small['correct']['document_tokens']) + 1 \
        <= small['serving']['slots']


def test_rehearsal_line_counts_the_latent_the_prefix_and_the_experts(config):
    env = {k: v for k, v in os.environ.items() if k != 'XLA_FLAGS'}
    proc = subprocess.run(
        [sys.executable, os.path.join(manifest.ROOT, 'benchmarks', 'run.py'),
         '--workload', CELL, '--seed', str(2**31 + 42), '--seconds', '2',
         '--trace', '1', '--rehearse'],
        cwd=manifest.ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line['correct'] is True and line['failed'] == 0
    got = line['metrics']
    assert got['latent_cache_mb.tpot']['value'] > 0
    assert 50 < got['prefix_reuse_share.tpot']['value'] <= 100
    assert 0 < got['moe_experts_touched_share.tpot']['value'] <= 100
    window = next(l for l in proc.stdout.splitlines()
                  if l.startswith('window '))
    counted = dict(kv.split('=') for kv in window.split()[1:])
    assert float(counted['prefix_hits']) > 0
    assert float(counted['latent_rows_read']) > 0
    assert float(counted['moe_pairs']) > 0
    assert float(counted['moe_pairs_dropped']) == 0
    assert float(counted['moe_prefill_pairs_dropped']) == 0
    assert 'followup_tokens_not_shared' in proc.stdout
    assert 'EXCEEDED' not in proc.stdout


def test_bf16_stored_control_reads_over_the_limits(config):
    """At the rehearse widths, nine layers deep (`control_test`): the
    control against the reference at the same matmul precision and at
    "highest", as serve_comparisons compares."""
    import jax.numpy as jnp
    from reference import axk1 as ref
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

    def row_median(a, b):
        a, b = np.asarray(a), np.asarray(b)
        return np.median(np.linalg.norm(a - b, axis=-1)
                         / np.linalg.norm(b, axis=-1))
    # not correct by the two limits that separate on the chip (the
    # whole-tensor ones leave room for rows that took another expert)
    assert row_median(control, truth) > limits['row_median_rel_l2_to_highest']
    assert row_median(control, same) > limits['decode_row_median_rel_l2']
    assert row_median(same, truth) < 0.01 * limits['decode_row_median_rel_l2']
