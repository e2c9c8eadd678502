"""The readers on a program whose chunks ride with the lanes (ROADMAP S14
(b): a step that carries a prefill chunk AND the decode lanes is one
execution), and on today's: every per-layer metric a serving cell lists
is read from a fabricated run of the joined shape, and on a run of
today's shape in which the slice's means equal the window's each of the
26 readers that PR 53 moved to the slice's own counts gives what its
file of PR 52 (tests/data/readers_pr52/) gave."""
import os

import pytest

from harness import gaps, manifest, spans
from tools import reread
from test_gap_metrics import _buffer
from test_spans import _serving_buffer

SERVING = ['gpt1b3_serve_chat', 'olmohyb_serve_long', 'nemo3s_serve_reason',
           'axk1_serve_docfollow', 'granite4hs_serve_sessions',
           'sthink21b_serve_mixed', 'solar2_serve_chat_shared']
PR52 = sorted(f[:-3] for f in os.listdir(reread.PR52))
STEP_OPS = ('kv_page_append', 'paged_decode_mask', 'gated_delta_step',
            'ssd_step', 'kda_step', 'paged_latent_attention',
            'paged_attention', 'paged_window_attention')
CHUNK_OPS = ('kv_page_write', 'paged_prefill_mask', 'gated_delta_chunk',
             'ssd_chunk', 'kda_chunk', 'paged_latent_prefill')
PT = 16


def _run(cell_name, joined):
    """A traced run of `cell_name` in plain data. 80 steps carried lanes
    in the slice; of them 20 carried a chunk too where `joined` (no pure
    prefill execution is left), else 20 chunks ran as programs of their
    own. A step held 20 lanes of 1537 tokens (97 pages), a chunk 180
    rows; the window's totals are ten slices at the same means."""
    man = manifest.check(manifest.load())
    cell, cfg = manifest.cell(man, cell_name)
    config = manifest.read_json(cfg['file'])
    steps, chunks, lanes, live = 80, 20, 20, 20 * (1 + 96 * PT)
    plain = steps - chunks if joined else steps
    layers = 12                      # expert sublayers a step: no reader's
    moe = {'layer_calls': layers, 'pairs': layers * lanes * 4,
           'experts_touched': layers * 7}
    moe_chunk = {'layer_calls': layers, 'pairs': layers * 180 * 4,
                 'experts_touched': layers * 8}
    lane_sums = {'decode_calls': 1, 'lanes': lanes, 'live_tokens': live,
                 'pages_read': lanes * 97, 'state_lanes': lanes,
                 'latent_rows': 5 * live, 'rows_read': live,
                 'window_rows_read': lanes * 1000}
    c = {'slice_' + k: steps * v for k, v in lane_sums.items()}
    c.update({'slice_plain_' + k: plain * v for k, v in lane_sums.items()})
    c.update(slice_prefill_calls=chunks, slice_state_tokens=chunks * 180,
             slice_prefill_tokens=chunks * 180)
    for k in moe:
        c['slice_moe_' + k] = steps * moe[k]
        c['slice_moe_prefill_' + k] = chunks * moe_chunk[k]
    # the window: ten slices
    c.update(decode_calls=10 * steps, prefill_calls=10 * chunks,
             live_tokens=10 * steps * live, state_lanes=10 * steps * lanes,
             prefill_tokens=10 * chunks * 180, decode_s=10 * steps * 0.011,
             prefill_s=0.0 if joined else 10 * chunks * 0.02,
             decode_batch_sum=10 * steps * lanes, decode_batch_count=10 * steps,
             kv_pages_in_use_max=3000, kv_live_pages_max=2000,
             compiles_in_window=0, preemptions=0, window_s=45.0,
             xla_compile_requests=0, setup_compile_misses=0,
             gen_late_p95_ms=1.5, ttft_p90_ms=400.0, ttft_p50_ms=200.0,
             recurrent_state_bytes_max=1_000_000_000, state_resets=50,
             ssm_state_bytes_max=1_000_000_000,
             state_snapshot_bytes_max=2_000_000_000, streams_opened=200,
             snapshots_taken=150, snapshots_adopted=190, snapshots_evicted=5,
             state_chunk_tokens=10 * chunks * 180, prefix_hits=180,
             prefix_tokens_reused=850, prompt_tokens_admitted=1000,
             latent_rows_read=10 * steps * 5 * live,
             latent_cache_bytes_max=3_000_000_000, window_pages_freed=4321,
             window_live_pages_max=3900, window_pages_in_use_max=5000,
             prefix_window_tail_miss=0, prefix_window_tail_adopted=90)
    for k in moe:
        c['moe_' + k] = 10 * steps * moe[k]
        c['moe_prefill_' + k] = 10 * chunks * moe_chunk[k]
    c['moe_pairs_dropped'] = c['moe_prefill_pairs_dropped'] = 0
    # the trace: 25 ms a step, 40 ms more where a chunk rides in it, 45 ms
    # a chunk alone; generous op times, so that every share stays a share
    mixed = chunks if joined else 0
    programs = {'decode': {'calls': steps - mixed,
                           'device_s': (steps - mixed) * 0.025},
                'prefill': {'calls': chunks - mixed,
                            'device_s': (chunks - mixed) * 0.045}}
    if joined:
        programs['decode+prefill'] = {'calls': mixed,
                                      'device_s': mixed * 0.065}
    if 'state_copy' in config['trace_programs']:
        programs['state_copy'] = {'calls': 6, 'device_s': 0.0012}
    op_runs = dict.fromkeys(STEP_OPS, steps)
    op_runs.update(dict.fromkeys(CHUNK_OPS, chunks))
    # an execution that carried both is one run of the experts' op
    op_runs.update(moe_experts=steps + chunks - mixed, state_row_copy=6)
    ops = {'moe_experts': 0.9, 'state_row_copy': 0.0011, 'mul': 0.4,
           'short_conv': 0.05, 'hlo:slice-done': 0.1, 'hlo:copy-done': 0.01}
    ops.update(dict.fromkeys(STEP_OPS, 0.5))
    ops.update(dict.fromkeys(CHUNK_OPS, 0.2))
    busy = sum(p['device_s'] for p in programs.values())
    served, plan, _ = _serving_buffer()
    idle = {'feed': 3.0, 'fetch': 1.0, 'elsewhere': 2.0, 'no_span': 0.5,
            'gaps': {}}
    return {'cell': cell, 'config': config, 'plan': plan, 'chips': 1,
            'e2e': {'tpot_p50_ms': 20.0, 'setup_s': 70.0}, 'counters': c,
            'setup': {'init': 5.0, 'build': 3.0, 'weights': 20.0,
                      'load': 30.0, 'warm': 12.0},
            'trace': {'window_s': 4.0, 'busy_s': busy, 'chips': 1,
                      'ops': ops, 'programs': programs, 'op_runs': op_runs,
                      'gaps': {}, 'span_calls': {}, 'collective_s': 0.0,
                      'collective_exposed_s': 0.0},
            'device': {'platform': 'tpu', 'kind': 'TPU v5 lite', 'count': 1,
                       'memory_peak_bytes': 13_000_000_000},
            '_program_spans': {'serving': spans.serving_view(served, plan),
                               'training': None, 'idle': idle},
            '_gap_view': gaps.view(*_buffer())}


@pytest.mark.parametrize('cell', SERVING)
def test_every_listed_metric_is_read_where_every_chunk_rides_with_lanes(
        cell, monkeypatch):
    man = manifest.check(manifest.load())
    run = _run(cell, joined=True)
    # the one reader that takes the process's span buffer as it stands
    served = [dict(s, overlapped=1) if s['name'] == 'paged.decode.tables'
              else s for s in _serving_buffer()[0]]
    monkeypatch.setattr(spans, 'program_spans', lambda: served)
    assert run['trace']['programs']['prefill']['calls'] == 0
    for m in manifest.metrics_of(man, 'per_layer', cell):
        value = manifest.layer_metric(man, m['name']).read(run)
        assert value is not None, m['name']
        if 'roofline' in m['name'] or 'mfu' in m['name']:
            assert 0.0 < value <= 100.0, (m['name'], value)
    assert manifest.layer_metric(man, 'prefill_share.tpot').read(run) == \
        pytest.approx(100 * 20 * 0.065 / run['trace']['busy_s'])
    # the decode program's own time is that of the steps with lanes alone
    assert manifest.layer_metric(man, 'decode_dev_ms.tpot').read(run) == \
        pytest.approx(25.0)


@pytest.mark.parametrize('cell', SERVING)
def test_on_todays_program_each_moved_reader_gives_what_its_file_of_pr52_gave(
        cell):
    """The slice's lanes, tokens and experts equal the window's means
    here; what differs between the two files is where they take them."""
    man = manifest.check(manifest.load())
    read = reread.both(_run(cell, joined=False), man, reread.PR52)
    assert read and set(read) <= set(PR52)
    for name, (old, new) in read.items():
        assert old is not None and new == pytest.approx(old, rel=1e-9), name


def test_the_26_moved_readers_are_those_kept_from_pr52():
    man = manifest.check(manifest.load())
    assert len(PR52) == 26
    listed = {m['name'] for cell in SERVING
              for m in manifest.metrics_of(man, 'per_layer', cell)}
    assert set(PR52) <= listed
