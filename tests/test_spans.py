"""The program's one span primitive (profiler.RecordEvent) and its two
sinks: obs/trace.py's bounded buffer on perf_counter(), and JAX's
profiler trace (`pt.<name>` annotations on the device trace's clock).

What must hold:

- a run of ServingEngine leaves, per request, serve.queue /
  serve.prefill / serve.decode spans that share the request's id and
  subtract exactly against the Request's own timestamps, one
  perf_counter() reading per token, and serve.iter spans whose children
  fit inside them;
- Executor.run leaves exe.run with exe.feed / exe.prepare /
  device_segment:* (and exe.fetch only when the caller fetched);
- with the registry off nothing is recorded; the buffer is bounded and
  says how many it dropped; FLAGS_obs_dir still yields the event log
  obs/report.py merges, on the epoch clock;
- no observability hook on the Executor's hot path waits for the
  device, reads the allocator or walks a scope.
"""
import collections
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import memory, profiler
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.obs import report, telemetry, trace
from paddle_tpu.serving import ServingEngine

from test_paged import _save_lm

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=32, use_tp=False, use_sp=False)


@pytest.fixture
def registry_on():
    """The registry (and so the span buffer) on, with no exporter and
    no event log; off and empty again afterwards."""
    telemetry.reset()
    trace.clear()
    telemetry.enable()
    yield
    telemetry.disable(final_flush=False)
    telemetry.reset()
    trace.clear()


@pytest.fixture(scope='module')
def lm_predictor(tmp_path_factory):
    return _save_lm(tmp_path_factory.mktemp('spans_lm'), CFG, 5)


def _decoder(lm_predictor, **kw):
    return lm_predictor.prepare_decoding(slots=2, page_tokens=4,
                                         prefill_chunk=8, **kw)


def _by_name(spans):
    out = collections.defaultdict(list)
    for s in spans:
        out[s['name']].append(s)
    return out


def _tiny_train():
    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = fluid.layers.data(name='x', shape=[8], dtype='float32')
        loss = fluid.layers.mean(fluid.layers.fc(input=x, size=4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss, {'x': np.ones((4, 8), 'float32')}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

@pytest.fixture
def two_requests(lm_predictor, registry_on):
    dec = _decoder(lm_predictor)
    with ServingEngine(dec) as eng:
        reqs = [eng.submit(list(range(1, 12)), max_new_tokens=6),
                eng.submit([3, 4, 5], max_new_tokens=5)]
        for r in reqs:
            r.result(120)
    return reqs, trace.spans()


def test_request_spans_share_the_request_id_and_its_clock(two_requests):
    reqs, spans = two_requests
    for req in reqs:
        mine = {s['name']: s for s in spans
                if s['kind'] == 'request' and s['sid'] == req.id}
        assert set(mine) == {'serve.queue', 'serve.prefill', 'serve.decode'}
        q, p, d = (mine[n] for n in ('serve.queue', 'serve.prefill',
                                     'serve.decode'))
        # the spans ARE the request's timestamps: same clock, no rounding
        assert (q['t0'], q['t1']) == (req.submitted_at, req.admitted_at)
        assert (p['t0'], p['t1']) == (req.admitted_at, req.first_token_at)
        assert (d['t0'], d['t1']) == (req.first_token_at, req.done_at)
        assert (q['t1'] - q['t0']) + (p['t1'] - p['t0']) == \
            pytest.approx(req.first_token_at - req.submitted_at, abs=1e-12)
        assert d['n_prompt'] == len(req.prompt)
        assert d['max_new_tokens'] == req.max_new_tokens
        assert d['n_tokens'] == len(req.tokens) and d['state'] == 'DONE'
        assert len(d['gaps_ms']) == len(req.tokens) - 1
        assert sum(d['gaps_ms']) == pytest.approx(
            1e3 * (req.token_at[-1] - req.token_at[0]))
    assert mine['serve.decode']['prefill_chunks'] == 1     # 3 tokens
    first = next(s for s in spans if s['name'] == 'serve.decode'
                 and s['sid'] == reqs[0].id)
    assert first['prefill_chunks'] == 2                    # 11 tokens, chunk 8


def test_request_carries_admission_and_per_token_times(two_requests):
    reqs, _ = two_requests
    for req in reqs:
        assert len(req.token_at) == len(req.tokens)
        assert req.token_at[0] == req.first_token_at
        assert req.token_at == sorted(req.token_at)
        assert req.submitted_at <= req.admitted_at <= req.first_token_at \
            <= req.token_at[-1] <= req.done_at
        assert req.preemptions == 0 and req.prefill_chunks >= 1


def test_iteration_spans_hold_their_children(two_requests):
    _, spans = two_requests
    names = _by_name(spans)
    iters = names['serve.iter']
    assert iters
    kids = collections.defaultdict(list)
    for s in spans:
        if s['psid'] is not None:
            kids[s['psid']].append(s)
    decoded = 0
    for it in iters:
        mine = kids[it['sid']]
        assert {'serve.admit', 'serve.prefill_tick'} <= \
            {s['name'] for s in mine}
        for s in mine:
            assert it['t0'] <= s['t0'] <= s['t1'] <= it['t1']
        assert sum(s['t1'] - s['t0'] for s in mine) <= it['t1'] - it['t0']
        assert {'lanes', 'ready', 'prefilling', 'queued'} <= set(it)
        seq = [s['name'] for s in mine if s['name'].startswith(
            ('serve.pack', 'paged.decode', 'exe.run', 'serve.accept'))]
        # the engine keeps one decode step in flight: a call dispatches
        # its step, then fetches the one before (nothing to accept
        # behind a burst's first); a step is collected without a call
        # where no lane is ready or every lane of it has ended
        collected = ['paged.decode.fetch', 'serve.accept']
        if it['ready']:
            decoded += 1
            assert seq[:5] == ['serve.pack', 'paged.decode.tables',
                               'exe.run', 'paged.decode.book',
                               'paged.decode.fetch']
            assert seq[5:] in ([], ['serve.accept'], collected,
                               ['serve.accept'] + collected)
        else:
            assert seq in ([], collected)
    assert decoded == len(names['paged.decode.tables']) \
        == len(names['paged.decode.book']) > 0
    # every step's ids are fetched once: by the next call, or collected
    assert len(names['paged.decode.fetch']) == decoded + sum(
        s['name'] == 'serve.accept' for s in spans) - sum(
        t['overlapped'] for t in names['paged.decode.tables'])
    assert sum(t['overlapped'] for t in names['paged.decode.tables']) > 0
    # a prompt's chunks: tables, exe.run and book each, a fetch only on
    # its last (11 tokens in chunks of 8, and 3 tokens: 3 chunks, 2 last)
    assert len(names['paged.prefill.tables']) == 3
    assert len(names['paged.prefill.book']) == 3
    assert len(names['paged.prefill.fetch']) == 2
    # the executor's spans nest under the decoder's call, in the loop
    tick = names['serve.prefill_tick'][0]
    run = next(s for s in names['exe.run'] if s['psid'] == tick['sid'])
    assert {s['name'].split(':')[0] for s in kids[run['sid']]} >= \
        {'exe.feed', 'exe.prepare'}


def test_a_request_cancelled_in_the_queue_leaves_its_wait(lm_predictor,
                                                          registry_on):
    eng = ServingEngine(_decoder(lm_predictor))      # never started
    req = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.cancel(req)
    eng.stop(drain=False)
    mine = [s for s in trace.spans() if s['kind'] == 'request']
    assert [s['name'] for s in mine] == ['serve.queue']
    assert mine[0]['state'] == 'CANCELLED' and mine[0]['sid'] == req.id
    assert (mine[0]['t0'], mine[0]['t1']) == (req.submitted_at, req.done_at)
    assert req.admitted_at is None and req.token_at == []


# ---------------------------------------------------------------------------
# executor and reader
# ---------------------------------------------------------------------------

def test_executor_run_spans(registry_on):
    main, startup, loss, feed = _tiny_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    trace.clear()
    exe.run(main, feed=feed, fetch_list=[loss])
    exe.run(main, feed=feed, fetch_list=[loss], return_numpy=False)
    spans = trace.spans()
    runs = [s for s in spans if s['name'] == 'exe.run']
    assert len(runs) == 2
    assert runs[0]['fingerprint'] == runs[1]['fingerprint']
    assert runs[0]['n_feeds'] == 1 and runs[0]['psid'] is None
    for run, fetched in zip(runs, (True, False)):
        kids = [s['name'] for s in spans if s['psid'] == run['sid']]
        assert kids[:2] == ['exe.feed', 'exe.prepare']
        assert ('exe.fetch' in kids) == fetched
    # the dispatch is the run's child; the compiling call (the first) is
    # inside its xla.compile span, once
    assert any(s['name'].startswith('device_segment:')
               for s in spans if s['psid'] == runs[1]['sid'])
    compiles = [s for s in spans if s['name'] == 'xla.compile']
    assert len(compiles) == 1 and compiles[0]['psid'] == runs[0]['sid']
    seg = next(s for s in spans if s['psid'] == compiles[0]['sid'])
    assert seg['name'].startswith('device_segment:')
    assert compiles[0]['fingerprint'] == runs[0]['fingerprint']


def test_reader_pop_says_how_long_it_waited(registry_on):
    main, startup = Program(), Program()
    with program_guard(main, startup):
        rdr = fluid.layers.py_reader(capacity=4, shapes=[(-1, 4)],
                                     dtypes=['float32'],
                                     use_double_buffer=True)
        x = fluid.layers.read_file(rdr)
        loss = fluid.layers.mean(x)

    def slow_source():
        for _ in range(3):
            time.sleep(0.05)
            yield [np.ones((2, 4), 'float32')]
    rdr.decorate_tensor_provider(slow_source)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    rdr.start()
    try:
        for _ in range(3):
            exe.run(main, fetch_list=[loss])
    finally:
        rdr.reset()
    pops = [s for s in trace.spans() if s['name'] == 'host_op:read']
    assert len(pops) == 3
    for s in pops:
        assert 0.0 <= s['waited_ms'] <= 1e3 * (s['t1'] - s['t0'])
    assert max(s['waited_ms'] for s in pops) > 10.0


# ---------------------------------------------------------------------------
# the switch, the bound, the two sinks
# ---------------------------------------------------------------------------

def test_registry_off_records_nothing(lm_predictor):
    assert not telemetry.enabled() and not trace.enabled()
    trace.clear()
    main, startup, loss, feed = _tiny_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    exe.run(main, feed=feed, fetch_list=[loss])
    with profiler.RecordEvent('off.scope', a=1) as ev:
        trace.annotate(b=2)
        trace.record_span('off.span', 'host', 1, 0.0, 1.0)
    with trace.span('off.cross') as sp:
        assert sp is None
    dec = _decoder(lm_predictor)
    with ServingEngine(dec) as eng:
        req = eng.submit([1, 2, 3], max_new_tokens=3)
        req.result(120)
    assert len(req.token_at) == 3            # the request's own times stay
    assert trace.spans() == [] and trace.current_sid() is None
    assert ev.attrs == {'a': 1}


def test_buffer_is_bounded_and_counts_what_it_drops(registry_on, monkeypatch):
    import tracemalloc
    bound = 1000
    monkeypatch.setattr(trace, 'BUFFER_SPANS', bound)
    monkeypatch.setattr(trace, '_buf', collections.deque(maxlen=bound))
    tracemalloc.start()
    before = tracemalloc.get_traced_memory()[0]
    for i in range(bound + 250):
        with profiler.RecordEvent('fill', i=i, lanes=9, ready=9):
            pass
    held = tracemalloc.get_traced_memory()[0] - before
    tracemalloc.stop()
    spans = trace.spans()
    assert len(spans) == bound
    assert [s['i'] for s in spans] == list(range(250, bound + 250))
    assert telemetry.snapshot()['counters']['trace.dropped'] == 250
    # what PERF.md and BUFFER_SPANS's comment state: under 500 bytes a
    # span with three attributes
    assert held / bound < 500, held / bound
    assert trace._buf.maxlen == bound


def test_the_real_bound_is_one_stated_number():
    assert trace._buf.maxlen == trace.BUFFER_SPANS == 1 << 17


def test_obs_dir_log_is_drained_at_flush_on_the_epoch_clock(tmp_path):
    d = str(tmp_path / 'obs')
    telemetry.reset()
    trace.clear()
    telemetry.enable(d, role='t0', period=60.0)
    trace.enable(d, role='t0')
    log = os.path.join(d, 'events-t0-%d.jsonl' % os.getpid())
    try:
        wall0 = time.time()
        with profiler.RecordEvent('outer', tag='x'):
            with profiler.RecordEvent('inner'):
                pass
        with trace.span('rpc.PING', kind='client') as sp:
            wire = trace.wire_trace(sp)
        trace.record_span('serve.queue', 'request', 7, 1.0, 2.0, state='DONE')
        wall1 = time.time()
        # nothing is written when a span ends...
        assert os.path.getsize(log) == 0 and len(trace.spans()) == 4
        telemetry.flush()
        # ...flush() moves the buffer into the log
        assert trace.spans() == []
        with open(log) as f:
            recs = [json.loads(ln) for ln in f]
    finally:
        trace.disable()
        telemetry.disable(final_flush=False)
        telemetry.reset()
    by = {r['name']: r for r in recs}
    assert set(by) == {'outer', 'inner', 'rpc.PING', 'serve.queue'}
    for r in recs:
        assert r['type'] == 'span' and r['role'] == 't0' \
            and r['pid'] == os.getpid()
    # the clock anchor: perf_counter readings became unix seconds
    for name in ('outer', 'inner', 'rpc.PING'):
        assert wall0 - 0.01 <= by[name]['t0'] <= by[name]['t1'] <= wall1 + 0.01
    assert by['inner']['psid'] == by['outer']['sid']
    assert by['outer']['tag'] == 'x' and by['outer']['kind'] == 'host'
    assert by['rpc.PING']['sid'] == wire['sid']          # rides the wire as is
    assert by['serve.queue']['sid'] != by['outer']['sid']
    assert by['serve.queue']['t1'] - by['serve.queue']['t0'] == \
        pytest.approx(1.0)
    # and obs/report.py reads it as it did
    events, _ = report.collect(str(tmp_path))
    assert {e['name'] for e in events if e.get('type') == 'span'} == set(by)
    tl = report.build_timeline(events)
    assert any(e.get('ph') == 'X' and e['name'] == 'inner'
               for e in tl['traceEvents'])


def test_spans_land_in_the_profilers_trace_under_pt(tmp_path, registry_on):
    """While JAX's profiler captures, a RecordEvent is a TraceAnnotation
    named 'pt.<name>' in the xplane's host plane: on the clock of the
    device's ops, which is what the idle split reads."""
    import glob
    import jax
    from jax.profiler import ProfileData
    with profiler.RecordEvent('before.capture'):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with profiler.RecordEvent('outer.scope'):
            with profiler.RecordEvent('inner.scope', n=3):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), '**', '*.xplane.pb'),
                      recursive=True)
    events = [(e.name, e.start_ns, e.duration_ns)
              for plane in ProfileData.from_file(files[-1]).planes
              for line in plane.lines for e in line.events
              if e.name.startswith('pt.')]
    names = [n for n, _, _ in events]
    assert sorted(names) == ['pt.inner.scope', 'pt.outer.scope']
    by = {n: (s, s + d) for n, s, d in events}
    assert by['pt.outer.scope'][0] <= by['pt.inner.scope'][0] \
        <= by['pt.inner.scope'][1] <= by['pt.outer.scope'][1]
    assert by['pt.inner.scope'][1] - by['pt.inner.scope'][0] >= 2_000_000
    # the buffer took all three, the capture only what ran inside it
    assert [s['name'] for s in trace.spans()] == \
        ['before.capture', 'inner.scope', 'outer.scope']


def test_parents_are_per_thread(registry_on):
    import threading
    seen = {}

    def work():
        with profiler.RecordEvent('thread.scope'):
            seen['sid'] = trace.current_sid()
    with profiler.RecordEvent('main.scope'):
        t = threading.Thread(target=work)
        t.start()
        t.join(10)
        assert not t.is_alive()
        main_sid = trace.current_sid()
    by = {s['name']: s for s in trace.spans()}
    assert by['thread.scope']['psid'] is None
    assert by['thread.scope']['sid'] == seen['sid'] != main_sid
    assert by['thread.scope']['tid'] != by['main.scope']['tid']


# ---------------------------------------------------------------------------
# the observatory neither waits nor reads on the hot path
# ---------------------------------------------------------------------------

class _Calls(object):
    def __init__(self):
        self.n = collections.Counter()

    def wrap(self, name, fn):
        def counted(*a, **kw):
            self.n[name] += 1
            return fn(*a, **kw)
        return counted


def _drive_executor(return_numpy):
    main, startup, loss, feed = _tiny_train()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    return lambda: exe.run(main, feed=feed, fetch_list=[loss],
                           return_numpy=return_numpy)


def _drive_parallel():
    main, startup, loss, feed = _tiny_train()
    feed = {'x': np.ones((8, 8), 'float32')}
    with program_guard(main, startup):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(use_cuda=False, loss_name=loss.name,
                                    main_program=main)
    return lambda: pe.run(fetch_list=[loss.name], feed=feed,
                          return_numpy=False)


@pytest.mark.parametrize('drive', ['executor_device_arrays',
                                   'executor_numpy', 'parallel_executor',
                                   'paged_decode_step'])
def test_no_hook_in_run_waits_or_reads_the_allocator(drive, registry_on,
                                                     monkeypatch,
                                                     lm_predictor):
    import jax
    if drive == 'paged_decode_step':
        dec = _decoder(lm_predictor)
        dec.open_stream(0, [1, 2, 3])
        assert dec.prefill_step(0) is not None
        pos = [3]

        def step():
            dec.decode_step(np.array([5, 0]), np.array([pos[0], 0]))
            pos[0] += 1
    elif drive == 'parallel_executor':
        step = _drive_parallel()
    else:
        step = _drive_executor(drive == 'executor_numpy')
    step()                                   # compile outside the count
    calls = _Calls()
    monkeypatch.setattr(jax, 'block_until_ready',
                        calls.wrap('block_until_ready',
                                   jax.block_until_ready))
    for name in ('memory_stats', 'hbm_snapshot', 'scope_footprint'):
        monkeypatch.setattr(memory, name,
                            calls.wrap(name, getattr(memory, name)))
    dev = jax.devices('cpu')[0]
    monkeypatch.setattr(type(dev), 'memory_stats',
                        calls.wrap('device.memory_stats',
                                   type(dev).memory_stats), raising=False)
    steps_before = telemetry._counters['perf.steps'].value
    for _ in range(5):
        step()
    assert telemetry._counters['perf.steps'].value == steps_before + 5
    assert not calls.n, dict(calls.n)
    # the gauges are still there for whoever asks: read on demand
    snap = telemetry.snapshot()
    assert calls.n['hbm_snapshot'] == 1
    assert snap['gauges']['hbm.scope_bytes'] > 0 \
        or snap['gauges']['hbm.bytes_in_use'] > 0


def test_step_latency_needs_a_fetch_that_waited(registry_on):
    step_arrays = _drive_executor(False)
    step_numpy = _drive_executor(True)
    base = telemetry.snapshot()['hists']['perf.step_latency']['count']
    for _ in range(3):
        step_arrays()
    assert telemetry.snapshot()['hists']['perf.step_latency']['count'] \
        == base
    for _ in range(3):
        step_numpy()
    snap = telemetry.snapshot()
    assert snap['hists']['perf.step_latency']['count'] == base + 3
    assert snap['hists']['perf.step_latency']['sum'] > 0
