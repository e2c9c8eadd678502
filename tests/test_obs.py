"""Observability suite: telemetry registry, cross-process trace spans,
and the merged cluster timeline/rollup (paddle_tpu/obs/).

What must hold:

- the registry is exact under concurrent writers and costs NOTHING
  while disabled (no lock, no allocation — it lives on the wire fast
  path);
- an RPC client span and the server's handler span share one span id
  across a real socket, carried by the optional `trace` meta field (no
  wire-version bump: an untraced peer just ignores it);
- obs/report.py merges per-role JSONL into one chrome trace with
  per-role lanes, client->server flow links, and a clock-offset
  estimate that actually re-aligns a skewed role;
- a faulted in-process cluster run with observability ON lands on
  BIT-EXACT fault-free weights while the retry / CRC-failure / dedup
  counters prove the faults really happened — observability observes,
  it never perturbs.
"""
import json
import os
import subprocess
import sys
import threading
import time
import tracemalloc

import numpy as np
import pytest

from paddle_tpu.distributed import resilience
from paddle_tpu.distributed.param_service import ParameterService
from paddle_tpu.distributed.resilience import FaultPlan, RetryPolicy
from paddle_tpu.distributed.rpc import PSClient, PSServer
from paddle_tpu.obs import report, telemetry, trace

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_WORKER = os.path.join(_HERE, 'ps_worker.py')
sys.path.insert(0, _HERE)


@pytest.fixture
def obs_on(tmp_path):
    """Telemetry + tracing into a tmp dir; always restored to the
    disabled default afterwards (other tests rely on zero overhead)."""
    d = str(tmp_path / 'obs')
    telemetry.reset()
    telemetry.enable(d, role='t0', period=60.0)
    trace.enable(d, role='t0')
    yield d
    trace.disable()
    telemetry.disable(final_flush=False)
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_counters_exact_under_concurrent_writers(obs_on):
    """8 threads x 5000 inc() on a SHARED counter (plus a per-thread
    one) lose nothing: the registry lock makes inc read-modify-write
    atomic."""
    shared = telemetry.counter('test.shared')
    h = telemetry.histogram('test.lat')
    n_threads, n_incs = 8, 5000

    def work(i):
        mine = telemetry.counter('test.t%d' % i)
        for _ in range(n_incs):
            shared.inc()
            mine.inc(2)
        h.observe(0.001 * (i + 1))

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = telemetry.snapshot()
    assert snap['counters']['test.shared'] == n_threads * n_incs
    for i in range(n_threads):
        assert snap['counters']['test.t%d' % i] == 2 * n_incs
    assert snap['hists']['test.lat']['count'] == n_threads
    assert snap['hists']['test.lat']['max'] == 0.008


class _ForbiddenLock(object):
    def __enter__(self):
        raise AssertionError('disabled-mode fast path took the lock')

    def __exit__(self, *exc):
        return False


def test_disabled_fast_path_no_lock_no_alloc(monkeypatch):
    """While disabled (the default), inc/set/observe return after ONE
    module-global bool read: the registry lock is never touched and the
    calls allocate nothing — safe on the per-frame wire path."""
    assert not telemetry.enabled()
    c = telemetry.counter('test.disabled_c')
    g = telemetry.gauge('test.disabled_g')
    h = telemetry.histogram('test.disabled_h')
    monkeypatch.setattr(telemetry, '_lock', _ForbiddenLock())
    for _ in range(100):    # warm up any lazy interpreter state
        c.inc()
        g.set(3)
        h.observe(0.5)
    tracemalloc.start()
    try:
        for _ in range(500):
            c.inc()
            g.set(7)
            h.observe(0.25)
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    ours = snap.filter_traces(
        [tracemalloc.Filter(True, telemetry.__file__)])
    assert sum(s.size for s in ours.statistics('lineno')) == 0
    assert c.value == 0 and g.value == 0 and h.count == 0


def test_histogram_buckets_and_reset_in_place(obs_on):
    h = telemetry.histogram('test.buckets')
    h.observe(5e-5)      # under the first bound (1e-4)
    h.observe(2e-4)      # second bucket
    h.observe(1e9)       # +Inf overflow bucket
    snap = telemetry.snapshot()['hists']['test.buckets']
    assert snap['count'] == 3
    assert snap['buckets'][0] == 1 and snap['buckets'][1] == 1
    assert snap['buckets'][-1] == 1
    assert snap['min'] == 5e-5 and snap['max'] == 1e9
    # reset zeros IN PLACE: the instrument object modules captured at
    # import keeps recording
    telemetry.reset()
    h.observe(1.0)
    assert telemetry.snapshot()['hists']['test.buckets']['count'] == 1


def test_exporter_appends_snapshot_lines(obs_on):
    telemetry.counter('test.exported').inc(3)
    telemetry.flush()
    telemetry.counter('test.exported').inc(4)
    telemetry.flush()
    fn = [f for f in os.listdir(obs_on) if f.startswith('metrics-t0-')]
    assert len(fn) == 1
    with open(os.path.join(obs_on, fn[0])) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    assert len(lines) == 2
    assert lines[0]['counters']['test.exported'] == 3
    assert lines[-1]['counters']['test.exported'] == 7
    assert lines[-1]['role'] == 't0'
    assert lines[-1]['pid'] == os.getpid()


# ---------------------------------------------------------------------------
# trace spans across real sockets
# ---------------------------------------------------------------------------

def _mini_service():
    params = {'w': np.zeros(4, 'f4')}

    def run_round(merged):
        for v in merged.values():
            params['w'] = params['w'] - np.asarray(v)

    svc = ParameterService(
        num_trainers=1, sync_mode=True,
        get_param=lambda name: params[name], run_round=run_round,
        rpc_deadline=60.0)
    return svc, params


def _fast_retry():
    return RetryPolicy(max_attempts=5, backoff=0.01, max_backoff=0.05,
                       reconnect_secs=5.0)


def _events_of(obs_dir):
    trace.flush()       # spans are buffered: move them into the log
    out = []
    for fn in sorted(os.listdir(obs_dir)):
        if fn.startswith('events-'):
            with open(os.path.join(obs_dir, fn)) as f:
                out.extend(json.loads(ln) for ln in f if ln.strip())
    return out


def test_span_propagation_across_real_sockets(obs_on):
    """One send_var over a real socket leaves a client span AND a
    server handler span SHARING a span id — the trace field rode the
    schemaless meta dict, no wire change."""
    svc, _ = _mini_service()
    srv = PSServer('127.0.0.1:0', svc)
    st = threading.Thread(target=srv.serve_forever, daemon=True)
    st.start()
    cli = PSClient('127.0.0.1:%d' % srv.port, trainer_id=0,
                   retry_policy=_fast_retry())
    cli.send_var('w@GRAD', np.ones(4, 'f4'))
    cli.batch_barrier()
    cli.get_var('w')
    cli.complete()
    st.join(timeout=10.0)
    assert not st.is_alive()

    events = _events_of(obs_on)
    clients = {e['sid']: e for e in events
               if e.get('kind') == 'client'}
    servers = {e['sid']: e for e in events
               if e.get('kind') == 'server'}
    linked = set(clients) & set(servers)
    assert len(linked) >= 4        # SEND_VAR, BARRIER, GET_VAR, COMPLETE
    sid = next(s for s in linked
               if clients[s]['name'] == 'rpc.SEND_VAR')
    assert servers[sid]['name'] == 'SEND_VAR'
    # the server span sits inside the client's request window (same
    # host, same clock)
    assert clients[sid]['t0'] <= servers[sid]['t0']
    assert servers[sid]['t1'] <= clients[sid]['t1'] + 1e-3


def test_untraced_peer_meta_ignored():
    """A request WITHOUT the trace field (tracing off) is served
    normally — the field is optional, not a protocol bump."""
    assert not trace.enabled()
    svc, params = _mini_service()
    srv = PSServer('127.0.0.1:0', svc)
    st = threading.Thread(target=srv.serve_forever, daemon=True)
    st.start()
    cli = PSClient('127.0.0.1:%d' % srv.port, trainer_id=0,
                   retry_policy=_fast_retry())
    cli.send_var('w@GRAD', np.ones(4, 'f4'))
    cli.batch_barrier()
    np.testing.assert_allclose(cli.get_var('w'), -np.ones(4, 'f4'))
    cli.complete()
    st.join(timeout=10.0)


# ---------------------------------------------------------------------------
# merge + clock alignment + rollup (synthetic logs)
# ---------------------------------------------------------------------------

def _write_jsonl(path, recs):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        for r in recs:
            f.write(json.dumps(r) + '\n')


def test_clock_offset_alignment_on_skewed_logs(tmp_path):
    """Role 'ps' logs with a clock +5s ahead of role 'tr'. The span-pair
    midpoints recover the skew and the merged timeline re-aligns the
    server span INSIDE its client span."""
    root = str(tmp_path)
    skew = 5.0
    cspans = [{'type': 'span', 'kind': 'client', 'name': 'rpc.SEND_VAR',
               'sid': 's%d' % i, 'psid': None, 't0': 100.0 + i,
               't1': 100.2 + i, 'tid': 1, 'role': 'tr', 'pid': 10}
              for i in range(3)]
    sspans = [{'type': 'span', 'kind': 'server', 'name': 'SEND_VAR',
               'sid': 's%d' % i, 'psid': None,
               't0': 100.05 + i + skew, 't1': 100.15 + i + skew,
               'tid': 2, 'role': 'ps', 'pid': 20}
              for i in range(3)]
    _write_jsonl(os.path.join(root, 'tr', 'events-tr-10.jsonl'), cspans)
    _write_jsonl(os.path.join(root, 'ps', 'events-ps-20.jsonl'), sspans)

    events, _ = report.collect(root)
    assert len(events) == 6
    offsets = report.estimate_offsets(events)
    assert offsets['tr'] == 0.0                 # reference: most clients
    assert abs(offsets['ps'] + skew) < 1e-6     # shifted back by 5s

    tl = report.build_timeline(events)
    lanes = {e['args']['name']: e['pid'] for e in tl['traceEvents']
             if e.get('ph') == 'M'}
    assert set(lanes) == {'tr', 'ps'}
    xs = [e for e in tl['traceEvents'] if e.get('ph') == 'X']
    c0 = next(e for e in xs if e['args'].get('sid') == 's0'
              and e['pid'] == lanes['tr'])
    s0 = next(e for e in xs if e['args'].get('sid') == 's0'
              and e['pid'] == lanes['ps'])
    assert c0['ts'] <= s0['ts'] <= c0['ts'] + c0['dur']   # re-aligned
    # flow link per pair, and the merged list is (ts, pid)-sorted
    assert sum(1 for e in tl['traceEvents'] if e.get('ph') == 's') == 3
    assert sum(1 for e in tl['traceEvents'] if e.get('ph') == 'f') == 3
    keys = [(e.get('ts', 0), e.get('pid', 0)) for e in tl['traceEvents']]
    assert keys == sorted(keys)


def test_rollup_sums_roles_and_incarnations(tmp_path):
    """Counters sum across a role's incarnations (restart = new pid =
    new file) and across roles into cluster totals; gauges take the
    latest snapshot; histograms merge."""
    root = str(tmp_path)
    h1 = {'count': 2, 'sum': 0.4, 'min': 0.1, 'max': 0.3,
          'buckets': [0] * 12}
    h2 = {'count': 1, 'sum': 0.5, 'min': 0.5, 'max': 0.5,
          'buckets': [0] * 12}
    _write_jsonl(os.path.join(root, 'tr', 'metrics-tr-10.jsonl'), [
        {'ts': 1.0, 'role': 'tr', 'pid': 10,
         'counters': {'rpc.client.retries': 2}, 'gauges': {'q': 5},
         'hists': {'lat': h1}},
        {'ts': 2.0, 'role': 'tr', 'pid': 10,
         'counters': {'rpc.client.retries': 4}, 'gauges': {'q': 3},
         'hists': {'lat': h1}},          # LAST line of the file wins
    ])
    _write_jsonl(os.path.join(root, 'tr', 'metrics-tr-11.jsonl'), [
        {'ts': 3.0, 'role': 'tr', 'pid': 11,
         'counters': {'rpc.client.retries': 1}, 'gauges': {'q': 7},
         'hists': {'lat': h2}}])         # the restarted incarnation
    _write_jsonl(os.path.join(root, 'ps', 'metrics-ps-20.jsonl'), [
        {'ts': 1.5, 'role': 'ps', 'pid': 20,
         'counters': {'rpc.client.retries': 10, 'ps.rounds_completed': 6},
         'gauges': {}, 'hists': {}}])

    _, metric_lasts = report.collect(root)
    ru = report.rollup(metric_lasts)
    assert ru['roles']['tr']['counters']['rpc.client.retries'] == 5
    assert ru['roles']['tr']['gauges']['q'] == 7     # latest ts (pid 11)
    assert ru['roles']['tr']['hists']['lat']['count'] == 3
    assert ru['roles']['tr']['hists']['lat']['max'] == 0.5
    assert ru['totals']['rpc.client.retries'] == 15
    assert ru['totals']['ps.rounds_completed'] == 6
    text = report.format_rollup_text(ru)
    assert 'rpc.client.retries' in text and 'tr:' in text


def test_timeline_tool_stable_sort_and_flow_passthrough(tmp_path):
    """tools/timeline.py round-trips a merged multi-process trace: the
    (ts, pid) sort is stable, and flow events keep ph/id/bp intact."""
    sys.path.insert(0, os.path.join(_ROOT, 'tools'))
    import timeline as timeline_tool

    merged = {'traceEvents': [
        {'ph': 'X', 'name': 'b', 'pid': 2, 'tid': 0, 'ts': 10.0,
         'dur': 1.0},
        {'ph': 'X', 'name': 'a', 'pid': 1, 'tid': 0, 'ts': 10.0,
         'dur': 2.0},
        {'ph': 's', 'name': 'rpc', 'cat': 'rpc', 'id': 'abc',
         'pid': 1, 'tid': 0, 'ts': 11.0},
        {'ph': 'f', 'bp': 'e', 'name': 'rpc', 'cat': 'rpc', 'id': 'abc',
         'pid': 2, 'tid': 0, 'ts': 11.0},
        {'ph': 'M', 'name': 'process_name', 'pid': 1,
         'args': {'name': 'tr'}},
    ]}
    src = str(tmp_path / 'merged.json')
    dst = str(tmp_path / 'tl.json')
    with open(src, 'w') as f:
        json.dump(merged, f)
    timeline_tool.convert(src, dst)
    with open(dst) as f:
        out = json.load(f)['traceEvents']
    keys = [(e.get('ts', 0), e.get('pid', 0)) for e in out]
    assert keys == sorted(keys)
    flow_s = next(e for e in out if e['ph'] == 's')
    flow_f = next(e for e in out if e['ph'] == 'f')
    assert flow_s['id'] == flow_f['id'] == 'abc'
    assert flow_f['bp'] == 'e'
    # equal ts: lower pid first (stable cross-lane order)
    x10 = [e['pid'] for e in out if e.get('ts') == 10.0]
    assert x10 == sorted(x10)

    # list-form input: events with an explicit ph pass through unmangled
    src2 = str(tmp_path / 'list.json')
    with open(src2, 'w') as f:
        json.dump([{'name': 'x', 'pid': 0, 'tid': 0, 'ts': 1.0,
                    'dur': 2.0},
                   {'name': 'rpc', 'ph': 's', 'id': 'z', 'pid': 0,
                    'tid': 0, 'ts': 2.0}], f)
    timeline_tool.convert(src2, dst)
    with open(dst) as f:
        out2 = json.load(f)['traceEvents']
    assert any(e.get('ph') == 's' and e.get('id') == 'z' for e in out2)


# ---------------------------------------------------------------------------
# chaos smoke: observed faulted run == fault-free weights, counters lit
# ---------------------------------------------------------------------------

def _faultable_round(cli, g):
    cli.send_var('w@GRAD', g)
    cli.batch_barrier()
    return cli.get_var('w')


def test_chaos_smoke_counters_fire_weights_bitexact(obs_on):
    """In-process mini cluster under a corrupt + close plan WITH
    observability on: the CRC-failure / retry / reconnect / dedup
    counters all fire, the fault events land in the trace, and the
    final weights are BIT-EXACTLY the fault-free run's."""
    g1 = np.ones(4, 'f4')
    g2 = 2 * np.ones(4, 'f4')

    def run(plan):
        svc, params = _mini_service()
        srv = PSServer('127.0.0.1:0', svc)
        st = threading.Thread(target=srv.serve_forever, daemon=True)
        st.start()
        ctx = resilience.active_plan(plan) if plan else None
        if ctx:
            ctx.__enter__()
        try:
            cli = PSClient('127.0.0.1:%d' % srv.port, trainer_id=0,
                           retry_policy=_fast_retry())
            _faultable_round(cli, g1)
            w = _faultable_round(cli, g2)
            cli.complete()
        finally:
            if ctx:
                ctx.__exit__(None, None, None)
        st.join(timeout=10.0)
        assert not st.is_alive()
        return np.asarray(w)

    w_clean = run(None)
    telemetry.reset()
    plan = FaultPlan([
        # send #1 corrupted on the wire: server CRC rejects, retry
        # resends clean (APPLY the replay)
        resilience.FaultRule('send', 1, 'corrupt', type='SEND_VAR'),
        # send #3 delivered then the conn closes pre-reply: the replay
        # must be DEDUPED server-side
        resilience.FaultRule('send', 3, 'close', type='SEND_VAR'),
    ])
    w_faulted = run(plan)

    assert np.array_equal(w_clean, w_faulted)   # bit-exact, not close
    snap = telemetry.snapshot()['counters']
    assert snap['wire.crc_failures'] >= 1
    assert snap['rpc.client.retries'] >= 2      # one per fired rule
    assert snap['rpc.client.reconnects'] >= 1   # close forced a redial
    assert snap['ps.dedup_replay_hits'] >= 1
    assert snap['ps.rounds_completed'] == 2
    assert snap['faults.injected'] == 2
    assert snap['wire.frames_out'] > 0 and snap['wire.bytes_out'] > 0


# ---------------------------------------------------------------------------
# acceptance: supervised kill+corrupt cluster -> one timeline + rollup
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.timeout(600)
def test_supervised_cluster_obs_report(tmp_path):
    """The ISSUE's acceptance run: a supervised 2x2 cluster where
    trainer0's plan corrupts a frame AND kills the process mid-run.
    tools-level merge must produce ONE chrome timeline with a lane per
    role and linked client/server span pairs, and a rollup whose
    retry / CRC-failure / restart counters are all >= 1."""
    import ps_worker  # noqa: F401 — asserts the harness is importable
    from paddle_tpu.distributed.supervisor import Supervisor

    def _free_ports(n):
        import socket as _s
        socks = [(_s.socket()) for _ in range(n)]
        for s in socks:
            s.bind(('127.0.0.1', 0))
        ports = [s.getsockname()[1] for s in socks]
        for s in socks:
            s.close()
        return ports

    obs_dir = str(tmp_path / 'obs')
    eps = ','.join('127.0.0.1:%d' % p for p in _free_ports(2))
    plan = FaultPlan([
        resilience.FaultRule('send', 2, 'corrupt', type='SEND_VAR'),
        resilience.FaultRule('send', 7, 'exit', type='SEND_VAR'),
    ])
    base_env = dict(os.environ)
    base_env.pop('JAX_PLATFORMS', None)
    base_env.pop('XLA_FLAGS', None)
    base_env.update({'PS_MODEL': 'mlp', 'PS_ENDPOINTS': eps,
                     'PS_TRAINERS': '2', 'PS_STEPS': '3',
                     'PS_SYNC': '1', 'PS_OPTIMIZER': 'sgd',
                     'FLAGS_rpc_deadline': '120',
                     'FLAGS_rpc_max_retries': '12',
                     'FLAGS_rpc_reconnect_secs': '10',
                     'FLAGS_obs_flush_secs': '0.5'})
    sup = Supervisor(max_restarts=2, backoff=0.5,
                     log_dir=str(tmp_path), obs_dir=obs_dir)
    for i in range(2):
        sup.add_role('pserver%d' % i, [sys.executable, _WORKER],
                     env=dict(base_env, PS_ROLE='pserver',
                              PS_PSERVER_ID=str(i)))
    for i in range(2):
        env = dict(base_env, PS_ROLE='trainer', PS_TRAINER_ID=str(i))
        if i == 0:
            env['FLAGS_fault_plan'] = plan.to_json()
        sup.add_role('trainer%d' % i, [sys.executable, _WORKER], env=env)
    sup.start()
    try:
        states = sup.wait(timeout=420)
        assert all(s == 'done' for s in states.values()), \
            (states, sup.output('trainer0')[-4000:])
        assert sup.restarts['trainer0'] >= 1
    finally:
        sup.stop()

    tl, ru = report.write_report(
        obs_dir, timeline_path=str(tmp_path / 'timeline.json'),
        rollup_path=str(tmp_path / 'rollup.json'))
    lanes = {e['args']['name'] for e in tl['traceEvents']
             if e.get('ph') == 'M'}
    assert {'trainer0', 'trainer1', 'pserver0', 'pserver1',
            'supervisor'} <= lanes
    s_ids = {e['id'] for e in tl['traceEvents'] if e.get('ph') == 's'}
    f_ids = {e['id'] for e in tl['traceEvents'] if e.get('ph') == 'f'}
    assert len(s_ids & f_ids) >= 1          # linked client/server pair
    totals = ru['totals']
    assert totals.get('rpc.client.retries', 0) >= 1
    assert totals.get('wire.crc_failures', 0) >= 1
    assert totals.get('supervisor.restarts', 0) >= 1
    assert totals.get('faults.injected', 0) >= 1


def test_obs_report_cli_runs(tmp_path):
    """tools/obs_report.py end to end on a synthetic obs root."""
    root = tmp_path / 'obs'
    _write_jsonl(str(root / 'tr' / 'events-tr-1.jsonl'), [
        {'type': 'span', 'kind': 'client', 'name': 'rpc.GET_VAR',
         'sid': 'q', 'psid': None, 't0': 1.0, 't1': 1.2, 'tid': 0,
         'role': 'tr', 'pid': 1}])
    _write_jsonl(str(root / 'tr' / 'metrics-tr-1.jsonl'), [
        {'ts': 1.0, 'role': 'tr', 'pid': 1,
         'counters': {'rpc.client.calls': 9}, 'gauges': {},
         'hists': {}}])
    tl_path = str(tmp_path / 'tl.json')
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, 'tools', 'obs_report.py'),
         '--obs_dir', str(root), '--timeline', tl_path],
        capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert 'rpc.client.calls' in r.stdout
    with open(tl_path) as f:
        tl = json.load(f)
    assert any(e.get('ph') == 'X' for e in tl['traceEvents'])
