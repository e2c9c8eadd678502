"""Device-side performance observatory (paddle_tpu/obs/perf.py +
obs/slo.py + the executor wiring).

What must hold:

- a two-step CPU train run lands xla.jit_cache hit/miss counts, a
  nonzero perf.step_latency histogram, and live hbm.* gauges in one
  registry snapshot, and the SECOND identical Executor.run is a pure
  cache hit — no new xla.compile span appears in the trace stream;
- ParallelExecutor keeps full jit_cache_stats parity with Executor and
  compiles the SPMD step exactly once across a steady-state loop;
- memory.estimate_program_memory upper-bounds what the framework
  actually holds after running the program (CPU-safe: allocator stats
  degrade to the scope footprint);
- histogram snapshots carry p50/p95/p99 derived from the exponential
  buckets, the report rollup ships percentiles instead of raw bucket
  dumps, and a torn metrics tail (kill -9 mid-write) merges with a
  warning instead of crashing;
- profiler device-op events round-trip into the merged chrome
  timeline as device lanes distinct from the host lanes, on the same
  clock;
- a deliberately breached SLO rule emits a slo.breach event;
- tools/perf_gate.py exits nonzero on a synthetically regressed
  fixture trajectory, and refuses to run with nothing to gate.
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import memory
from paddle_tpu.obs import perf, report, slo, telemetry, trace

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_PERF_GATE = os.path.join(_ROOT, 'tools', 'perf_gate.py')


@pytest.fixture
def obs_on(tmp_path):
    """Telemetry + tracing into a tmp dir; always restored to the
    disabled default afterwards (other tests rely on zero overhead)."""
    d = str(tmp_path / 'obs')
    telemetry.reset()
    perf._reset_for_tests()
    telemetry.enable(d, role='t0', period=60.0)
    trace.enable(d, role='t0')
    yield d
    trace.disable()
    telemetry.disable(final_flush=False)
    telemetry.reset()
    perf._reset_for_tests()
    slo.stop_global()


def _events(obs_dir):
    trace.flush()       # spans are buffered: move them into the log
    out = []
    for dirpath, _, files in os.walk(obs_dir):
        for fn in files:
            if fn.startswith('events-'):
                with open(os.path.join(dirpath, fn)) as f:
                    out.extend(json.loads(l) for l in f if l.strip())
    return out


def _tiny_train(bs=4):
    x = fluid.layers.data(name='x', shape=[8], dtype='float32')
    y = fluid.layers.fc(input=x, size=4)
    loss = fluid.layers.mean(y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    feed = {'x': np.ones((bs, 8), dtype='float32')}
    return loss, feed


# ---------------------------------------------------------------------------
# compile/JIT + step telemetry through the Executor
# ---------------------------------------------------------------------------

def test_two_step_train_emits_perf_telemetry(obs_on):
    """The headline acceptance path: two identical train steps -> jit
    hit+miss counts, nonzero step latency, live hbm gauges, and the
    second run adds NO new xla.compile span."""
    loss, feed = _tiny_train()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    exe.run(feed=feed, fetch_list=[loss])

    snap = telemetry.snapshot()
    assert snap['counters']['xla.jit_cache.miss'] >= 2  # startup+main
    compiles_before = [e for e in _events(obs_on)
                       if e.get('name') == 'xla.compile']
    assert compiles_before, 'first run must trace xla.compile spans'
    for e in compiles_before:
        assert e.get('fingerprint'), 'span must carry a fingerprint'
    assert snap['hists']['xla.compile_latency']['count'] == \
        snap['counters']['xla.jit_cache.miss']

    exe.run(feed=feed, fetch_list=[loss])   # identical -> pure hit
    snap = telemetry.snapshot()
    assert snap['counters']['xla.jit_cache.hit'] >= 1
    compiles_after = [e for e in _events(obs_on)
                      if e.get('name') == 'xla.compile']
    assert len(compiles_after) == len(compiles_before), \
        'cache hit must not emit a new compile span'

    # live step attribution: every run is counted, and the two
    # whose fetch came back to the host (the startup program
    # fetches nothing) have a latency
    assert snap['hists']['perf.step_latency']['count'] == 2
    assert snap['hists']['perf.step_latency']['sum'] > 0
    assert snap['counters']['perf.steps'] == 3
    # hbm gauges live even on CPU (scope-footprint fallback): the
    # fc weight/bias are persistable device arrays by now
    assert snap['gauges']['hbm.bytes_in_use'] > 0
    assert snap['gauges']['hbm.watermark_bytes'] >= \
        snap['gauges']['hbm.bytes_in_use']
    assert snap['gauges']['hbm.scope_bytes'] > 0

    stats = exe.jit_cache_stats()
    assert stats['segment_misses'] == stats['compiled_segments']
    assert stats['segment_hits'] >= 1


def test_prepared_program_fingerprint_and_cost(obs_on):
    loss, feed = _tiny_train()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    exe.run(feed=feed, fetch_list=[loss])
    prepared = [p for k, p in exe._prepared_cache.items()
                if k[0] != 'block_run']
    assert all(p.fingerprint for p in prepared)
    # each run's span carries its prepared program's fingerprint
    runs = [s['fingerprint'] for s in trace.spans()
            if s['name'] == 'exe.run']
    assert runs == [p.fingerprint for p in prepared]


def test_disabled_mode_records_nothing():
    """With obs off, the same run must leave the registry untouched
    (the hooks are on the Executor hot path)."""
    telemetry.reset()
    loss, feed = _tiny_train()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    exe.run(feed=feed, fetch_list=[loss])
    telemetry.enable()
    try:
        snap = telemetry.snapshot()
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()
    assert snap['counters']['perf.steps'] == 0
    assert snap['hists']['perf.step_latency']['count'] == 0


def test_parallel_executor_compile_once_spmd(obs_on):
    """jit_cache_stats parity on the SPMD path: steady-state training
    compiles each segment exactly once; later steps are pure hits."""
    loss, feed = _tiny_train(bs=8)
    startup_exe = fluid.Executor()
    startup_exe.run(fluid.default_startup_program())
    pe = fluid.ParallelExecutor(
        use_cuda=False, loss_name=loss.name,
        main_program=fluid.default_main_program())
    pe.run(fetch_list=[loss.name], feed=feed)
    stats1 = pe.jit_cache_stats()
    assert set(stats1) == {'prepared_programs', 'compiled_segments',
                           'segment_hits', 'segment_misses'}
    assert stats1['compiled_segments'] >= 1
    assert stats1['segment_misses'] == stats1['compiled_segments']
    for _ in range(3):
        pe.run(fetch_list=[loss.name], feed=feed)
    stats2 = pe.jit_cache_stats()
    assert stats2['compiled_segments'] == stats1['compiled_segments'], \
        'SPMD steady state must not recompile'
    assert stats2['segment_hits'] >= stats1['segment_hits'] + 3
    snap = telemetry.snapshot()
    assert snap['counters']['xla.jit_cache.hit'] >= 3


# ---------------------------------------------------------------------------
# memory estimator vs live stats
# ---------------------------------------------------------------------------

def test_estimate_bounds_live_footprint(obs_on):
    """estimate_program_memory (analytic upper bound) must dominate
    what the framework actually holds for the same program, and the
    run must surface live hbm.* gauges in the snapshot."""
    loss, feed = _tiny_train()
    est = memory.estimate_program_memory(
        fluid.default_main_program(), batch_size=4)
    assert est['params'] > 0 and est['total'] >= est['params']
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    exe.run(feed=feed, fetch_list=[loss])
    actual = memory.max_memory_allocated()   # CPU: scope footprint
    assert actual > 0
    assert est['total'] >= actual, \
        'analytic estimate must upper-bound the live footprint ' \
        '(est=%d actual=%d)' % (est['total'], actual)
    snap = telemetry.snapshot()
    for g in ('hbm.bytes_in_use', 'hbm.peak_bytes',
              'hbm.scope_bytes', 'hbm.watermark_bytes'):
        assert g in snap['gauges']
        assert snap['gauges'][g] > 0, g


def test_hbm_snapshot_shape():
    snap = memory.hbm_snapshot()
    assert set(snap) == {'bytes_in_use', 'peak_bytes', 'bytes_limit',
                         'scope_bytes'}
    assert snap['peak_bytes'] >= snap['bytes_in_use']


# ---------------------------------------------------------------------------
# percentiles + torn-tail merge
# ---------------------------------------------------------------------------

def test_histogram_snapshot_percentiles(obs_on):
    h = telemetry.histogram('test.pct')
    for v in [0.001] * 50 + [0.010] * 45 + [0.100] * 5:
        h.observe(v)
    d = telemetry.snapshot()['hists']['test.pct']
    assert d['p50'] is not None
    assert d['min'] <= d['p50'] <= d['p95'] <= d['p99'] <= d['max']
    # the mass sits at 1ms / 10ms / 100ms: p50 must be in the low
    # bucket's range, p99 near the top
    assert d['p50'] < 0.01
    assert d['p99'] > 0.01


def test_hist_quantile_single_sample():
    d = {'count': 1, 'min': 0.005, 'max': 0.005, 'sum': 0.005,
         'buckets': [0, 0, 0, 1] + [0] * 8}
    assert telemetry.hist_quantile(d, 0.5) == pytest.approx(0.005)
    assert telemetry.hist_quantile({'count': 0, 'buckets': []},
                                   0.99) is None


def test_rollup_ships_percentiles_not_buckets(tmp_path):
    d = str(tmp_path / 'obs')
    os.makedirs(d)
    telemetry.reset()
    telemetry.enable(d, role='r0', period=60.0)
    try:
        h = telemetry.histogram('test.roll')
        for v in (0.001, 0.002, 0.004, 0.2):
            h.observe(v)
        telemetry.flush()
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()
    _, metric_lasts = report.collect(d)
    ru = report.rollup(metric_lasts)
    hd = ru['roles']['r0']['hists']['test.roll']
    assert 'buckets' not in hd
    assert hd['p50'] is not None and hd['p99'] is not None
    assert hd['min'] <= hd['p50'] <= hd['p99'] <= hd['max']
    text = report.format_rollup_text(ru)
    assert 'p50=' in text and 'p99=' in text


def test_torn_metrics_tail_warns_not_crashes(tmp_path):
    d = str(tmp_path / 'obs')
    os.makedirs(d)
    good = {'ts': 1.0, 'role': 'r0', 'counters': {'c': 3},
            'gauges': {}, 'hists': {}}
    with open(os.path.join(d, 'metrics-r0-1.jsonl'), 'w') as f:
        f.write(json.dumps(good) + '\n')
        f.write(json.dumps(good)[:25])   # kill -9 mid-write
    with pytest.warns(UserWarning, match='torn tail'):
        _, metric_lasts = report.collect(d)
    assert len(metric_lasts) == 1
    assert report.rollup(metric_lasts)['totals']['c'] == 3


# ---------------------------------------------------------------------------
# device lanes in the merged timeline
# ---------------------------------------------------------------------------

def test_device_lanes_round_trip(tmp_path):
    """Synthetic device-op events (the profiler.device_op_events
    4-tuple shape) must land in the chrome trace as device lanes
    distinct from the host lane, clock-aligned without an offset."""
    base = 1700000000.0     # host spans stamp unix time.time()
    host = [{'type': 'span', 'kind': 'host', 'name': 'step',
             'sid': 'h1', 't0': base, 't1': base + 0.010,
             'tid': 0, 'role': 'trainer0', 'pid': 10}]
    dev_events = [
        ('fusion.1', int((base + 0.002) * 1e9), 1_000_000,
         '/device:TPU:0'),
        ('mul.3', int((base + 0.004) * 1e9), 2_000_000,
         '/device:TPU:1'),
    ]
    recs = report.device_events_to_records(dev_events)
    assert all(r['kind'] == 'device' for r in recs)
    tl = report.build_timeline(host + recs)
    lanes = {e['args']['name']: e['pid'] for e in tl['traceEvents']
             if e.get('ph') == 'M'}
    assert 'trainer0' in lanes
    assert 'device:TPU:0' in lanes and 'device:TPU:1' in lanes
    assert len({lanes['trainer0'], lanes['device:TPU:0'],
                lanes['device:TPU:1']}) == 3, 'lanes must be distinct'
    spans = {e['name']: e for e in tl['traceEvents']
             if e.get('ph') == 'X'}
    assert spans['fusion.1']['cat'] == 'device'
    # same clock family: the device op started 2ms into the host step
    assert spans['fusion.1']['ts'] - spans['step']['ts'] == \
        pytest.approx(2000, abs=1)
    assert spans['mul.3']['dur'] == pytest.approx(2000, abs=1)


def test_write_report_merges_xplane_dir(tmp_path, monkeypatch):
    """write_report(xplane_dir=...) pulls device lanes through
    profiler.device_op_events (stubbed: no real capture on CPU)."""
    d = str(tmp_path / 'obs')
    os.makedirs(d)
    with open(os.path.join(d, 'events-t0-1.jsonl'), 'w') as f:
        f.write(json.dumps({'type': 'span', 'kind': 'host',
                            'name': 'host_op', 'sid': 'a', 't0': 5.0,
                            't1': 5.5, 'tid': 0, 'role': 't0',
                            'pid': 1}) + '\n')
    from paddle_tpu import profiler
    monkeypatch.setattr(
        profiler, 'device_op_events',
        lambda xdir, op_map=None, with_plane=False:
            [('conv2d.0', int(5.1e9), 50_000_000, '/device:TPU:0')])
    tl, _ = report.write_report(d, xplane_dir=str(tmp_path))
    names = [e['name'] for e in tl['traceEvents']
             if e.get('ph') == 'X']
    assert 'host_op' in names and 'conv2d.0' in names
    cats = {e['name']: e['cat'] for e in tl['traceEvents']
            if e.get('ph') == 'X'}
    assert cats['conv2d.0'] == 'device'


# ---------------------------------------------------------------------------
# SLO watchdog
# ---------------------------------------------------------------------------

def test_slo_breach_emits_event(obs_on):
    """A rule breached on purpose -> slo.breach in the event stream +
    the slo.breaches counter; a satisfied rule stays silent."""
    telemetry.gauge('test.slo.mfu').set(0.10)
    h = telemetry.histogram('test.slo.lat')
    for _ in range(10):
        h.observe(0.5)
    wd = slo.SLOWatchdog(slo.parse_rules(json.dumps([
        {'name': 'mfu_floor', 'metric': 'test.slo.mfu',
         'kind': 'gauge_min', 'threshold': 0.45},
        {'name': 'lat_p99', 'metric': 'test.slo.lat',
         'kind': 'p99_max', 'threshold': 0.010, 'min_count': 5},
        {'name': 'ok_rule', 'metric': 'test.slo.mfu',
         'kind': 'gauge_max', 'threshold': 0.90},
    ])))
    breaches = wd.check_now()
    assert {b['rule'] for b in breaches} == {'mfu_floor', 'lat_p99'}
    mfu_breach = next(b for b in breaches if b['rule'] == 'mfu_floor')
    assert mfu_breach['value'] == pytest.approx(0.10)
    assert mfu_breach['threshold'] == pytest.approx(0.45)
    evs = [e for e in _events(obs_on) if e.get('type') == 'slo.breach']
    assert len(evs) == 2
    assert {e['rule'] for e in evs} == {'mfu_floor', 'lat_p99'}
    snap = telemetry.snapshot()
    assert snap['counters']['slo.breaches'] == 2
    assert snap['gauges']['slo.breaching'] == 2


def test_slo_rate_rule_needs_two_checks(obs_on):
    c = telemetry.counter('test.slo.tokens')
    wd = slo.SLOWatchdog([slo.SLORule(
        'tok_floor', 'test.slo.tokens', 'rate_min', 1e9)])
    assert wd.check_now() == []     # first check only primes
    c.inc(100)
    breaches = wd.check_now()       # 100 tokens over ~0s << 1e9/s
    assert [b['rule'] for b in breaches] == ['tok_floor']


def test_slo_min_count_suppresses_cold_registry(obs_on):
    telemetry.histogram('test.slo.cold').observe(9.0)
    wd = slo.SLOWatchdog([slo.SLORule(
        'cold', 'test.slo.cold', 'p99_max', 0.001, min_count=5)])
    assert wd.check_now() == []


def test_watchdog_from_flags(obs_on, tmp_path):
    rules_path = str(tmp_path / 'rules.json')
    with open(rules_path, 'w') as f:
        json.dump([{'name': 'r', 'metric': 'g', 'kind': 'gauge_min',
                    'threshold': 1.0}], f)
    assert slo.watchdog_from_flags() is None    # default: no rules
    fluid.set_flags({'FLAGS_slo_rules': '@' + rules_path})
    try:
        wd = slo.watchdog_from_flags()
        assert wd is not None
        assert wd.rules[0].name == 'r'
    finally:
        fluid.set_flags({'FLAGS_slo_rules': ''})


# ---------------------------------------------------------------------------
# perf gate CLI
# ---------------------------------------------------------------------------

def _gate(*argv):
    return subprocess.run([sys.executable, _PERF_GATE] + list(argv),
                          capture_output=True, text=True, cwd=_ROOT)


def test_perf_gate_smoke():
    out = _gate('--smoke')
    assert out.returncode == 0, out.stdout + out.stderr
    assert 'smoke: ok' in out.stdout


def test_perf_gate_needs_something_to_gate():
    """The repository commits no trajectory: a bare invocation is an
    error, not a vacuous pass."""
    out = _gate()
    assert out.returncode == 2, out.stdout + out.stderr
    assert 'nothing to gate' in out.stderr


def test_perf_gate_trips_on_regressed_fixture(tmp_path):
    for n, metrics in ((1, {'mfu': 0.30, 'tokens_per_sec': 1000.0}),
                       (2, {'mfu': 0.21, 'tokens_per_sec': 990.0})):
        with open(str(tmp_path / ('BENCH_r%02d.json' % n)), 'w') as f:
            json.dump({'n': n, 'parsed': metrics}, f)
    out = _gate('--bench-glob', str(tmp_path / 'BENCH_r*.json'))
    assert out.returncode == 1, out.stdout + out.stderr
    assert 'REGRESSION mfu' in out.stdout
    # tokens only dipped 1% — inside tolerance, must not be flagged
    assert 'tokens_per_sec' not in \
        [l.split()[1] for l in out.stdout.splitlines()
         if 'REGRESSION' in l]


def test_perf_gate_candidate_mode(tmp_path):
    cand = str(tmp_path / 'cand.json')
    with open(cand, 'w') as f:
        json.dump({'mfu': 0.29, 'new_metric_per_sec': 5.0}, f)
    ref = str(tmp_path / 'BENCH_r01.json')
    with open(ref, 'w') as f:
        json.dump({'n': 1, 'parsed': {'mfu': 0.30}}, f)
    out = _gate('--candidate', cand, '--bench-glob',
                str(tmp_path / 'BENCH_r*.json'))
    assert out.returncode == 0, out.stdout   # 3% dip inside tolerance


# ---------------------------------------------------------------------------
# bench_suite --quick feed (slow: two real model builds + compiles)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_bench_suite_quick_stamps_gauges():
    out = subprocess.run(
        [sys.executable, os.path.join(_ROOT, 'tools', 'bench_suite.py'),
         '--quick', '--json', '--model', 'mnist', '--steps', '2'],
        capture_output=True, text=True, cwd=_ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.splitlines()[-1])
    row = rows[0]
    assert row['model'] == 'mnist' and 'error' not in row
    assert row['compile_ms'] > 0
    assert row['hbm_peak'] > 0
    assert 'decode_speedup' not in row   # subprocess extras skipped
