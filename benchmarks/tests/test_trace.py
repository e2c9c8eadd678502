"""The reduction from plain trace events to what the layer metrics read:
hand-made events with known answers, then a small recorded trace of the
chip (tests/data/, written by run.py --record-trace and cut to size)."""
import json
import os

import pytest

from harness import trace

DEV0, DEV1, HOST = '/device:TPU:0', '/device:TPU:1', '/host:CPU'
US = 1000


def _op(plane, name, start_us, dur_us):
    return (plane, trace.OPS_LINE, '%%%s = f32[8]{0} fusion(...)' % name,
            start_us * US, dur_us * US)


def _module(plane, name, start_us, dur_us):
    return (plane, trace.MODULES_LINE, name, start_us * US, dur_us * US)


def _span(name, start_us, dur_us):
    return (HOST, 'python', name, start_us * US, dur_us * US)


def test_busy_idle_self_times_and_gaps():
    events = [
        _module(DEV0, 'jit_step(1)', 0, 100),
        _op(DEV0, 'fusion.1', 0, 10),
        _op(DEV0, 'while.2', 20, 50),          # contains its body
        _op(DEV0, 'fusion.3', 20, 20),
        _op(DEV0, 'fusion.4', 45, 25),
        _op(DEV0, 'copy.5', 90, 10),
        _span('bench.step', 0, 80),
        _span('bench.step', 85, 30),
    ]
    labels = {'jit_step(1)': {'fusion.1': 'mul', 'fusion.3': 'mul',
                              'fusion.4': 'softmax'}}
    red = trace.reduce_events(events, window_s=200e-6, labels=labels)
    assert red['chips'] == 1
    assert red['busy_s'] == pytest.approx(70e-6)       # 10 + 50 + 10
    assert 1 - red['busy_s'] / red['window_s'] == pytest.approx(0.65)
    assert red['ops'] == pytest.approx({
        'mul': 30e-6, 'softmax': 25e-6, 'hlo:while': 5e-6,
        'hlo:copy': 10e-6})
    assert sum(red['ops'].values()) == pytest.approx(red['busy_s'])
    # gaps 10..20 (inside the first span) and 70..90 (its middle, 80, is
    # where the first span ends: still inside it)
    assert red['gaps'] == pytest.approx({'in:bench.step': 30e-6})
    assert red['span_calls'] == {'bench.step': 2}
    top = trace.breakdown(red)
    assert top['device_ops'][0] == ['mul', pytest.approx(30e-6)]


def test_gap_between_spans_is_named_after_the_last_one():
    events = [_op(DEV0, 'fusion.1', 0, 10), _op(DEV0, 'fusion.2', 50, 10),
              _span('bench.decode_step', 0, 12),
              _span('bench.prefill_step', 48, 12)]
    red = trace.reduce_events(events, window_s=1e-3)
    assert red['gaps'] == pytest.approx({'after:bench.decode_step': 40e-6})
    red = trace.reduce_events(events[:2], window_s=1e-3)
    assert list(red['gaps']) == ['no_benchmark_span']


def test_exposed_collective_and_mean_over_chips():
    def chip(plane, overlap):
        evs = [_op(plane, 'fusion.1', 0, 40),
               (plane, trace.OPS_LINE,
                '%all-reduce.7 = f32[8]{0} all-reduce(...)', 40 * US,
                20 * US)]
        if overlap:     # a compute op on another line under the collective
            evs.append(_op(plane, 'fusion.9', 45, 10))
        return evs
    red = trace.reduce_events(chip(DEV0, False) + chip(DEV1, True),
                              window_s=100e-6)
    assert red['chips'] == 2
    assert red['collective_s'] == pytest.approx(20e-6)
    # chip 0: all 20 us exposed; chip 1: 10 of 20 covered by compute
    assert red['collective_exposed_s'] == pytest.approx((20e-6 + 10e-6) / 2)
    assert red['busy_s'] == pytest.approx(60e-6)


def test_programs_are_named_by_their_marker_ops():
    events = [
        _module(DEV0, 'jit_seg_fn(11)', 0, 30),
        _op(DEV0, 'fusion.1', 0, 30),
        _module(DEV0, 'jit_seg_fn(22)', 40, 10),
        _op(DEV0, 'fusion.1', 40, 10),       # same name, another module
        _module(DEV0, 'jit_seg_fn(22)', 60, 12),
        _op(DEV0, 'fusion.1', 60, 12),
    ]
    labels = {'jit_seg_fn(11)': {'fusion.1': 'kv_page_write'},
              'jit_seg_fn(22)': {'fusion.1': 'kv_page_append'}}
    red = trace.reduce_events(
        events, 1e-3, labels,
        programs={'decode': ['kv_page_append'], 'prefill': ['kv_page_write']})
    assert red['programs']['prefill'] == {'calls': 1,
                                          'device_s': pytest.approx(30e-6)}
    assert red['programs']['decode'] == {'calls': 2,
                                         'device_s': pytest.approx(22e-6)}
    assert red['ops'] == pytest.approx({'kv_page_write': 30e-6,
                                        'kv_page_append': 22e-6})


STEP_LABELS = {
    'jit_decode(1)': {'fusion.1': 'ssd_step', 'fusion.2': 'mul'},
    'jit_prefill(2)': {'fusion.1': 'ssd_chunk', 'fusion.2': 'mul'},
    'jit_mixed(3)': {'fusion.1': 'ssd_chunk', 'fusion.2': 'ssd_step',
                     'fusion.3': 'mul'}}
# as every serving configuration lists them: `decode` first
STEP_MARKERS = {'decode': ['ssd_step'], 'prefill': ['ssd_chunk']}


def test_a_step_that_carried_a_chunk_and_the_lanes_goes_by_its_joined_name():
    """Every chunk rides with lanes: two pure decode executions, two that
    hold `ssd_chunk` AND `ssd_step`, none pure `prefill`. The joined
    executions are not filed under the first listed program that
    matches, both ops' executions are counted whatever program they ran
    in, and a step dispatched through another method than the two of
    today has its `bench.*` span, so no gap is `no_benchmark_span`."""
    events = []
    for t in (0, 300):
        events += [_module(DEV0, 'jit_decode(1)', t, 60),
                   _op(DEV0, 'fusion.1', t, 20),
                   _op(DEV0, 'fusion.2', t + 20, 40),
                   _span('bench.decode_step', t - 5, 30)]
    for t in (100, 200):
        events += [_module(DEV0, 'jit_mixed(3)', t, 90),
                   _op(DEV0, 'fusion.1', t, 40),
                   _op(DEV0, 'fusion.2', t + 40, 20),
                   _op(DEV0, 'fusion.3', t + 60, 30),
                   _span('bench.mixed_step', t - 8, 30)]
    red = trace.reduce_events(events, 400e-6, STEP_LABELS, STEP_MARKERS)
    assert red['programs'] == {
        'decode': {'calls': 2, 'device_s': pytest.approx(120e-6)},
        'prefill': {'calls': 0, 'device_s': 0.0},
        'decode+prefill': {'calls': 2, 'device_s': pytest.approx(180e-6)}}
    assert red['op_runs'] == {'ssd_step': 4, 'ssd_chunk': 2, 'mul': 4}
    assert red['ops'] == pytest.approx({'ssd_step': 80e-6, 'ssd_chunk': 80e-6,
                                        'mul': 140e-6})
    assert trace.carried(red['programs'], 'prefill') == {
        'calls': 2, 'device_s': pytest.approx(180e-6)}
    assert trace.carried(red['programs'], 'decode')['calls'] == 4
    assert red['gaps'] and 'no_benchmark_span' not in red['gaps']
    assert set(red['gaps']) == {'after:bench.decode_step',
                                'in:bench.mixed_step',
                                'in:bench.decode_step'}


def test_two_pure_programs_reduce_as_before_the_joined_name():
    """Today's program: three decode steps and one prefill chunk, each a
    program of its own. `programs`, `ops` and `gaps` key by key as the
    reduction of PR 52 gave them for this list (its output, pasted)."""
    events = []
    for t in (0, 100, 300):
        events += [_module(DEV0, 'jit_decode(1)', t, 60),
                   _op(DEV0, 'fusion.1', t, 20),
                   _op(DEV0, 'fusion.2', t + 20, 40),
                   _span('bench.decode_step', t - 5, 30)]
    events += [_module(DEV0, 'jit_prefill(2)', 200, 80),
               _op(DEV0, 'fusion.1', 200, 50), _op(DEV0, 'fusion.2', 250, 30),
               _span('bench.prefill_step', 190, 40)]
    red = trace.reduce_events(events, 400e-6, STEP_LABELS, STEP_MARKERS)
    before = {
        'busy_s': 0.00026,
        'gaps': {'after:bench.decode_step': 8e-05,
                 'after:bench.prefill_step': 2e-05},
        'ops': {'mul': 0.00015000000000000001, 'ssd_chunk': 5e-05,
                'ssd_step': 6.000000000000001e-05},
        'programs': {'decode': {'calls': 3, 'device_s': 0.00018},
                     'prefill': {'calls': 1, 'device_s': 8e-05}},
        'span_calls': {'bench.decode_step': 3, 'bench.prefill_step': 1}}
    for key, want in before.items():
        assert red[key] == want, key
    assert red['op_runs'] == {'ssd_step': 3, 'ssd_chunk': 1, 'mul': 4}
    assert trace.carried(red['programs'], 'prefill') == \
        red['programs']['prefill']


def test_labels_from_hlo_picks_each_modules_own_text():
    text_a = '''HloModule jit_seg_fn
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(seg_fn)/mul.3/dot_general" source_file="x.py"}
  %copy.2 = f32[8]{0} copy(%fusion.1)
'''
    text_b = '''HloModule jit_seg_fn
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(seg_fn)/jit(main)/softmax.12/reduce"}
  ROOT %fusion.7 = f32[8]{0} fusion(%fusion.1), kind=kLoop, metadata={op_name="jit(seg_fn)/layer_norm.4/mul"}
'''
    events = [_module(DEV0, 'jit_seg_fn(11)', 0, 30),
              _op(DEV0, 'fusion.1', 0, 10), _op(DEV0, 'copy.2', 10, 5),
              _module(DEV0, 'jit_seg_fn(22)', 40, 30),
              _op(DEV0, 'fusion.1', 40, 10), _op(DEV0, 'fusion.7', 50, 5)]
    labels = trace.labels_from_hlo(events, [text_b, text_a])
    assert labels == {'jit_seg_fn(11)': {'fusion.1': 'mul'},
                      'jit_seg_fn(22)': {'fusion.1': 'softmax',
                                         'fusion.7': 'layer_norm'}}


def test_a_helper_of_another_name_takes_no_program_s_labels():
    """`jit__with_first` (serving/paged.py: a last chunk's token put in
    front of a decode step) shares instruction names with the executor's
    programs; it is no execution of any of them, and no run of their
    ops."""
    text = '''HloModule jit_seg_fn, is_scheduled=true
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(seg_fn)/ssd_chunk.3/dot_general"}
  %copy.2 = f32[8]{0} copy(%fusion.1)
'''
    events = [_module(DEV0, 'jit_seg_fn(11)', 0, 30),
              _op(DEV0, 'fusion.1', 0, 10), _op(DEV0, 'copy.2', 10, 5),
              _module(DEV0, 'jit__with_first(22)', 40, 5),
              _op(DEV0, 'fusion.1', 40, 5)]
    labels = trace.labels_from_hlo(events, [text])
    assert labels == {'jit_seg_fn(11)': {'fusion.1': 'ssd_chunk'},
                      'jit__with_first(22)': {}}
    red = trace.reduce_events(events, 1e-3, labels,
                              {'prefill': ['ssd_chunk']})
    assert red['programs']['prefill']['calls'] == 1
    assert red['op_runs'] == {'ssd_chunk': 1, 'hlo:copy': 1, 'hlo:fusion': 1}
    # where the two sides name no module alike, names decide nothing
    other = [e[:2] + (e[2].replace('jit_seg_fn', 'pjit_step'),) + e[3:]
             if e[1] == trace.MODULES_LINE else e for e in events[:3]]
    assert trace.labels_from_hlo(other, [text]) == {
        'pjit_step(11)': {'fusion.1': 'ssd_chunk'}}


RECORDED = os.path.join(os.path.dirname(__file__), 'data',
                        'train_step_v5e.json')


@pytest.mark.skipif(not os.path.exists(RECORDED), reason='no recorded trace')
def test_recorded_train_trace():
    """Two steps of gpt1b3_train on one v5e chip (PR 23's chip run)."""
    with open(RECORDED) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec['events']]
    red = trace.reduce_events(events, rec['window_s'], rec['labels'])
    assert red['chips'] == 1
    assert 0 < red['busy_s'] <= red['window_s']
    assert sum(red['ops'].values()) == pytest.approx(red['busy_s'], rel=1e-6)
    for key, want in rec['expected'].items():
        got = red[key] if key in red else red['ops'][key[len('ops.'):]]
        assert got == pytest.approx(want, rel=1e-9), key
    top = [name for name, _ in trace.breakdown(red)['device_ops'][:3]]
    assert top[:2] == ['mul_grad', 'mul']
