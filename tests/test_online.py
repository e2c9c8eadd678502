"""Online learning subsystem (paddle_tpu/online/): versioned
trainer→serving parameter refresh.

The contract under test (ISSUE 9 acceptance):
- pservers publish a monotonically increasing, digest-stamped param
  version per closed optimizer round (async: per applied grad), and
  GET_VERSION/GET_VARS read a version-consistent shard image;
- a serving-range client (rpc.SERVING_TID_BASE) shares no dedup space
  with trainers and its COMPLETE can never shut a pserver down;
- the ParamSubscriber reassembles DistributeTranspiler row blocks,
  digest-verifies every pulled value, and installs at an engine step
  boundary — a failed/corrupt pull leaves the old verified version
  serving (quarantine-and-fall-back, checkpoint/restore.py style);
- mid-stream weight swaps land ONLY at decode-step boundaries: an
  identity swap leaves the token stream bit-exact, a real swap
  switches the stream at one boundary and never blends versions;
- staleness is observable: serving.staleness_rounds climbs while
  refresh is stalled and an SLO gauge_max rule pages on it;
- end to end: a Supervisor-run trainer x pserver x serving cluster
  where the serving process's installed params digest-match the
  pserver fleet's version-N manifest with NO serving restart.
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.distributed import resilience, rpc, wire
from paddle_tpu.distributed.param_service import ParameterService
from paddle_tpu.distributed.resilience import (FaultPlan, RetryPolicy)
from paddle_tpu.distributed.rpc import PSClient, PSServer
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.integrity import crc32
from paddle_tpu.models.transformer import (TransformerConfig,
                                           language_model_logits)
from paddle_tpu.online import ParamSubscriber, RefreshError

_HERE = os.path.dirname(os.path.abspath(__file__))
_WORKER = os.path.join(_HERE, 'online_worker.py')
sys.path.insert(0, _HERE)

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=16, use_tp=False, use_sp=False)


def _digest(value):
    return crc32(wire._payload_of(np.asarray(value))[1])


# ---------------------------------------------------------------------------
# version publication (service level)
# ---------------------------------------------------------------------------

def _versioned_service(sync_mode=True, num_trainers=1,
                       params=None):
    params = params if params is not None else {
        'w': np.arange(4, dtype='f4'), 'b': np.ones(2, 'f4')}

    def run_round(merged):
        for name, v in merged.items():
            p = name[:-len('@GRAD')]
            params[p] = params[p] - np.asarray(v)

    def run_one_grad(name, value):
        p = name[:-len('@GRAD')]
        params[p] = params[p] - np.asarray(value)

    svc = ParameterService(
        num_trainers=num_trainers, sync_mode=sync_mode,
        get_param=lambda name: params[name], run_round=run_round,
        run_one_grad=run_one_grad, rpc_deadline=60.0,
        param_names=sorted(params))
    return svc, params


def test_version_bumps_once_per_sync_round():
    svc, params = _versioned_service()
    assert svc.on_get_version(0) == {'version': 0}
    g = np.ones(4, 'f4')
    for r in range(3):
        svc.on_send_var('w@GRAD', 0, g, seq=('c1', 2 * r + 1))
        svc.on_batch_barrier(0, seq=('c1', 2 * r + 2))
        assert svc.on_get_version(0)['version'] == r + 1
    # a REPLAYED barrier closes no round and publishes no version
    svc.on_batch_barrier(0, seq=('c1', 6))
    assert svc.on_get_version(0)['version'] == 3


def test_version_bumps_per_applied_async_grad():
    svc, params = _versioned_service(sync_mode=False)
    g = np.ones(4, 'f4')
    svc.on_send_var('w@GRAD', 0, g, seq=('c1', 1))
    assert svc.on_get_version(0)['version'] == 1
    svc.on_send_var('w@GRAD', 0, g, seq=('c1', 1))   # dedup: no apply
    assert svc.on_get_version(0)['version'] == 1
    svc.on_send_var('w@GRAD', 0, g, seq=('c1', 2))
    assert svc.on_get_version(0)['version'] == 2


def test_manifest_digests_track_param_bytes():
    """The manifest is the digest of the CURRENT wire bytes of each
    hosted param, cached per version and invalidated on every bump."""
    svc, params = _versioned_service()
    m0 = svc.on_get_version(0, with_manifest=True)['manifest']
    assert sorted(m0) == ['b', 'w']
    assert m0['w'] == _digest(params['w'])
    svc.on_send_var('w@GRAD', 0, np.ones(4, 'f4'), seq=('c1', 1))
    svc.on_batch_barrier(0, seq=('c1', 2))
    m1 = svc.on_get_version(0, with_manifest=True)['manifest']
    assert m1['w'] == _digest(params['w'])
    assert m1['w'] != m0['w']
    assert m1['b'] == m0['b']        # untouched param, same bytes


def test_get_vars_reads_version_consistent_image():
    svc, params = _versioned_service()
    version, items = svc.on_get_vars(['w', 'b'], 0)
    assert version == 0
    got = {e['name']: (e['digest'], v) for e, v in items}
    for name in ('w', 'b'):
        assert got[name][0] == _digest(params[name])
        np.testing.assert_array_equal(got[name][1], params[name])


def test_snapshot_restores_param_version(tmp_path):
    path = str(tmp_path / 'ps.state')
    params = {'w': np.zeros(4, 'f4')}

    def make():
        def run_round(merged):
            for v in merged.values():
                params['w'] = params['w'] - np.asarray(v)
        return ParameterService(
            num_trainers=1, sync_mode=True,
            get_param=lambda n: params[n], run_round=run_round,
            rpc_deadline=60.0, param_names=['w'], snapshot_path=path,
            snapshot_every=1, dump_state=lambda: dict(params),
            load_state=lambda p: params.update(
                {k: np.asarray(v) for k, v in p.items()}))

    svc = make()
    for r in range(2):
        svc.on_send_var('w@GRAD', 0, np.ones(4, 'f4'),
                        seq=('c1', 2 * r + 1), inc=0, round_idx=r)
        svc.on_batch_barrier(0, seq=('c1', 2 * r + 2), inc=0,
                             round_idx=r)
    assert svc.on_get_version(0)['version'] == 2
    svc2 = make()
    # the restarted shard re-publishes the version it died at — a
    # subscriber must never see the version clock run backwards
    assert svc2.on_get_version(0)['version'] == 2


def test_serving_complete_is_inert():
    """A serving-range COMPLETE must not count toward pserver shutdown:
    close_all_clients(send_complete=True) in a serving process would
    otherwise kill the fleet mid-training."""
    svc, _ = _versioned_service(num_trainers=1)
    assert svc.on_complete(rpc.SERVING_TID_BASE) is False
    assert not svc._done_tids
    # the real trainer's COMPLETE still shuts the shard down
    assert svc.on_complete(0) is True


# ---------------------------------------------------------------------------
# wire roundtrip over real sockets (serving client range)
# ---------------------------------------------------------------------------

def _fast_retry():
    return RetryPolicy(max_attempts=2, backoff=0.01, max_backoff=0.05,
                       reconnect_secs=5.0)


def _serve(svc):
    srv = PSServer('127.0.0.1:0', svc)
    st = threading.Thread(target=srv.serve_forever, daemon=True)
    st.start()
    return srv, st


def test_get_version_get_vars_over_sockets():
    svc, params = _versioned_service()
    srv, st = _serve(svc)
    cli = PSClient('127.0.0.1:%d' % srv.port,
                   trainer_id=rpc.SERVING_TID_BASE,
                   retry_policy=_fast_retry())
    try:
        out = cli.get_version(with_manifest=True)
        assert out['version'] == 0
        assert sorted(out['manifest']) == ['b', 'w']
        version, entries, values = cli.get_vars(['w', 'b'])
        assert version == 0
        assert [e['name'] for e in entries] == ['w', 'b']
        np.testing.assert_array_equal(values[0], params['w'])
        np.testing.assert_array_equal(values[1], params['b'])
        for e, v in zip(entries, values):
            assert crc32(wire._payload_of(v)[1]) == e['digest']
        # pipelined async variants resolve identically
        assert cli.get_version_async().result(10.0)['version'] == 0
        v2, e2, _ = cli.get_vars_async(['b']).result(10.0)
        assert (v2, e2[0]['name']) == (0, 'b')
    finally:
        cli.close()
        # a trainer COMPLETE shuts the server down; the serving-range
        # traffic above must not have tripped it early
        assert st.is_alive()
        tcli = PSClient('127.0.0.1:%d' % srv.port, trainer_id=0,
                        retry_policy=_fast_retry())
        tcli.complete()
        tcli.close()
        st.join(timeout=10.0)
        assert not st.is_alive()


# ---------------------------------------------------------------------------
# ParamSubscriber unit: reassembly, digests, tolerance (fake clients)
# ---------------------------------------------------------------------------

class _FakePredictor(object):
    def __init__(self, served):
        self.served = dict(served)        # name -> shape
        self.installed = {}
        self.installs = 0

    def param_names(self):
        return sorted(self.served)

    def stage_weights(self, params):
        for name, val in params.items():
            if name not in self.served:
                raise KeyError(name)
            if tuple(np.asarray(val).shape) != self.served[name]:
                raise ValueError(name)
        return dict(params)

    def install_weights(self, staged):
        self.installed.update(staged)
        self.installs += 1


class _FakeFuture(object):
    def __init__(self, value):
        self._value = value

    def result(self, timeout=None):
        if isinstance(self._value, Exception):
            raise self._value
        return self._value


class _FakeClient(object):
    """Per-endpoint stand-in for rpc.get_serving_client: serves a fixed
    {block: array} shard at a fixed version, optionally tampering the
    digest of one block (the corrupt-pull surface)."""

    def __init__(self, shard, version, tamper=None):
        self.shard, self.version, self.tamper = shard, version, tamper

    def _manifest(self):
        return {n: _digest(v) for n, v in self.shard.items()}

    def get_version_async(self, with_manifest=False):
        out = {'version': self.version}
        if with_manifest:
            out['manifest'] = self._manifest()
        return _FakeFuture(out)

    def get_vars_async(self, names):
        entries, values = [], []
        for n in names:
            d = self._manifest()[n]
            if n == self.tamper:
                d ^= 0xFFFF
            entries.append({'name': n, 'digest': d})
            values.append(self.shard[n])
        return _FakeFuture((self.version, entries, values))


def _fake_fleet(monkeypatch, shards):
    """shards: {endpoint: _FakeClient}; routes the subscriber's client
    acquisition to the fakes."""
    monkeypatch.setattr(rpc, 'get_serving_client',
                        lambda ep, sid=0: shards[ep])


def test_subscriber_reassembles_row_blocks(monkeypatch):
    rng = np.random.RandomState(0)
    w = rng.rand(6, 3).astype('f4')
    b = rng.rand(2).astype('f4')
    _fake_fleet(monkeypatch, {
        'a:1': _FakeClient({'w.block0': w[:3], 'b': b}, version=4),
        'b:2': _FakeClient({'w.block1': w[3:]}, version=4)})
    pred = _FakePredictor({'w': (6, 3), 'b': (2,)})
    sub = ParamSubscriber(['a:1', 'b:2'], pred)
    assert sub.refresh_once() == 4
    assert sub.installed_version == 4 and sub.staleness_rounds() == 0
    np.testing.assert_array_equal(pred.installed['w'], w)
    np.testing.assert_array_equal(pred.installed['b'], b)
    assert pred.installs == 1
    assert sub.stats()['refreshes'] == 1


def test_subscriber_reports_oldest_shard_version(monkeypatch):
    """Mixed-version installs are tolerated (async-update semantics)
    but reported at the OLDEST contributing version, so staleness
    never under-counts."""
    _fake_fleet(monkeypatch, {
        'a:1': _FakeClient({'w': np.ones((2, 2), 'f4')}, version=7),
        'b:2': _FakeClient({'b': np.ones(2, 'f4')}, version=5)})
    pred = _FakePredictor({'w': (2, 2), 'b': (2,)})
    sub = ParamSubscriber(['a:1', 'b:2'], pred)
    assert sub.refresh_once() == 5
    assert sub.published_version == 7
    assert sub.staleness_rounds() == 2


def test_subscriber_corrupt_digest_keeps_old_version(monkeypatch):
    w = np.ones((2, 2), 'f4')
    good = _FakeClient({'w': w}, version=1)
    _fake_fleet(monkeypatch, {'a:1': good})
    pred = _FakePredictor({'w': (2, 2)})
    sub = ParamSubscriber(['a:1'], pred)
    assert sub.refresh_once() == 1
    good.shard['w'] = 2 * w
    good.version, good.tamper = 2, 'w'
    with pytest.raises(RefreshError, match='digest mismatch'):
        sub.refresh_once()
    # the old verified version is still installed and still reported
    np.testing.assert_array_equal(pred.installed['w'], w)
    assert sub.installed_version == 1
    assert sub.stats()['failures'] == 1
    assert 'digest mismatch' in sub.stats()['last_error']
    # the fault clears -> the NEXT cycle installs version 2
    good.tamper = None
    assert sub.refresh_once() == 2
    np.testing.assert_array_equal(pred.installed['w'], 2 * w)


def test_subscriber_skips_unserved_params(monkeypatch):
    """Pserver-only params (e.g. a mod-sharded distributed lookup
    table the decode graph replaced) are skipped, not fatal."""
    _fake_fleet(monkeypatch, {
        'a:1': _FakeClient({'w': np.ones((2, 2), 'f4'),
                            'table.block0': np.ones((8, 4), 'f4')},
                           version=1)})
    pred = _FakePredictor({'w': (2, 2)})
    sub = ParamSubscriber(['a:1'], pred)
    assert sub.refresh_once() == 1
    assert sorted(pred.installed) == ['w']


def test_subscriber_rejects_gapped_blocks_and_missing_params(
        monkeypatch):
    pred = _FakePredictor({'w': (4, 2)})
    _fake_fleet(monkeypatch, {
        'a:1': _FakeClient({'w.block0': np.ones((2, 2), 'f4'),
                            'w.block2': np.ones((2, 2), 'f4')},
                           version=1)})
    sub = ParamSubscriber(['a:1'], pred)
    with pytest.raises(RefreshError, match='non-contiguous'):
        sub.refresh_once()
    assert pred.installs == 0
    _fake_fleet(monkeypatch, {
        'a:1': _FakeClient({'b': np.ones(2, 'f4')}, version=1)})
    pred2 = _FakePredictor({'w': (4, 2), 'b': (2,)})
    sub2 = ParamSubscriber(['a:1'], pred2)
    with pytest.raises(RefreshError, match='missing served'):
        sub2.refresh_once()
    assert pred2.installs == 0


# ---------------------------------------------------------------------------
# refresh over real sockets + FaultPlan corrupt on the pull reply
# ---------------------------------------------------------------------------

def _socket_fleet(monkeypatch, svc):
    """One real PSServer; the subscriber acquires FRESH fast-retry
    serving-range clients each cycle (mirrors the pool's evict-on-fail
    contract without cross-test pool state)."""
    srv, st = _serve(svc)
    clients = []

    def fresh(ep, sid=0):
        c = PSClient(ep, trainer_id=rpc.SERVING_TID_BASE + sid,
                     retry_policy=_fast_retry())
        clients.append(c)
        return c

    monkeypatch.setattr(rpc, 'get_serving_client', fresh)
    return srv, st, clients


def _shutdown_fleet(srv, st, clients):
    for c in clients:
        try:
            c.close()
        except Exception:
            pass
    tcli = PSClient('127.0.0.1:%d' % srv.port, trainer_id=0,
                    retry_policy=_fast_retry())
    tcli.complete()
    tcli.close()
    st.join(timeout=10.0)
    assert not st.is_alive()


def test_refresh_over_sockets_bit_exact(monkeypatch):
    svc, params = _versioned_service()
    srv, st, clients = _socket_fleet(monkeypatch, svc)
    try:
        pred = _FakePredictor({'w': (4,), 'b': (2,)})
        sub = ParamSubscriber(['127.0.0.1:%d' % srv.port], pred)
        svc.on_send_var('w@GRAD', 0, np.ones(4, 'f4'), seq=('c1', 1))
        svc.on_batch_barrier(0, seq=('c1', 2))
        sub.poll_published()
        assert sub.published_version == 1
        assert sub.refresh_once() == 1
        np.testing.assert_array_equal(pred.installed['w'], params['w'])
        np.testing.assert_array_equal(pred.installed['b'], params['b'])
    finally:
        _shutdown_fleet(srv, st, clients)


def test_corrupt_pull_keeps_old_version_serving(monkeypatch):
    """FaultPlan corrupt on the GET_VARS reply (REPLY_VAR): with the
    rule stacked past the retry budget the pull genuinely fails, the
    subscriber raises RefreshError, and the previously installed
    version keeps serving; with the plan cleared the next cycle
    installs the new version. The satellite-3 acceptance."""
    svc, params = _versioned_service()
    srv, st, clients = _socket_fleet(monkeypatch, svc)
    try:
        pred = _FakePredictor({'w': (4,), 'b': (2,)})
        sub = ParamSubscriber(['127.0.0.1:%d' % srv.port], pred)
        assert sub.refresh_once() == 0
        w0 = pred.installed['w'].copy()
        svc.on_send_var('w@GRAD', 0, np.ones(4, 'f4'), seq=('c1', 1))
        svc.on_batch_barrier(0, seq=('c1', 2))
        # every retry of the pull eats one rule; _fast_retry allows 2
        # attempts, so 3 stacked rules exhaust the budget for sure
        plan = FaultPlan([
            resilience.FaultRule('send', n, 'corrupt',
                                 type='REPLY_VAR', bits=4)
            for n in (1, 2, 3)])
        with resilience.active_plan(plan):
            with pytest.raises(RefreshError):
                sub.refresh_once()
        np.testing.assert_array_equal(pred.installed['w'], w0)
        assert sub.installed_version == 0
        assert sub.stats()['failures'] == 1
        # plan cleared: the old version was never poisoned and the
        # next cycle converges on version 1
        assert sub.refresh_once() == 1
        np.testing.assert_array_equal(pred.installed['w'], params['w'])
    finally:
        _shutdown_fleet(srv, st, clients)


# ---------------------------------------------------------------------------
# staleness observability + SLO breach when refresh stalls
# ---------------------------------------------------------------------------

def test_staleness_gauge_and_slo_breach_when_stalled(monkeypatch):
    from paddle_tpu.obs import telemetry
    from paddle_tpu.obs.slo import SLOWatchdog, parse_rules
    svc, params = _versioned_service()
    srv, st, clients = _socket_fleet(monkeypatch, svc)
    telemetry.enable()
    try:
        telemetry.reset()
        pred = _FakePredictor({'w': (4,), 'b': (2,)})
        sub = ParamSubscriber(['127.0.0.1:%d' % srv.port], pred)
        dog = SLOWatchdog(parse_rules(json.dumps([
            {'name': 'serving_staleness',
             'metric': 'serving.staleness_rounds',
             'kind': 'gauge_max', 'threshold': 2}])))
        sub.refresh_once()
        assert dog.check_now() == []
        sub.pause()                     # refresh artificially stalled
        for r in range(4):              # training keeps publishing
            svc.on_send_var('w@GRAD', 0, np.ones(4, 'f4'),
                            seq=('c1', 2 * r + 1))
            svc.on_batch_barrier(0, seq=('c1', 2 * r + 2))
        sub.poll_published()            # paused: measures, no install
        assert sub.staleness_rounds() == 4
        snap = telemetry.snapshot()
        assert snap['gauges']['serving.staleness_rounds'] == 4
        breaches = dog.check_now()
        assert [b['rule'] for b in breaches] == ['serving_staleness']
        assert breaches[0]['value'] == 4
        sub.resume()
        sub.refresh_once()
        assert sub.staleness_rounds() == 0
        assert dog.check_now() == []
    finally:
        telemetry.disable()
        telemetry.reset()
        _shutdown_fleet(srv, st, clients)


# ---------------------------------------------------------------------------
# step-boundary swap semantics on the real decode engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def lm_predictor(tmp_path_factory):
    tmp = tmp_path_factory.mktemp('online_lm')
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = 7
    with unique_name.guard(), program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, CFG.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        logits = language_model_logits(toks, CFG)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits],
                                      exe, main_program=prog)
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    return AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))


def _current_weights(dec):
    return {n: np.asarray(dec._weight_scope.find_var(n))
            for n in dec.param_names()}


def test_stage_install_weights_roundtrip(lm_predictor):
    dec = lm_predictor.prepare_decoding(slots=2)
    cur = _current_weights(dec)
    assert cur, 'decode predictor serves no params?'
    staged = dec.stage_weights(cur)
    dec.install_weights(staged)
    for n, v in cur.items():
        np.testing.assert_array_equal(
            np.asarray(dec._weight_scope.find_var(n)), v)
    with pytest.raises(KeyError, match='unknown param'):
        dec.stage_weights({'bogus': np.zeros(3, 'f4')})
    name = next(iter(cur))
    bad = np.zeros(np.asarray(cur[name]).shape + (2,), 'f4')
    with pytest.raises(ValueError, match='shape mismatch'):
        dec.stage_weights({name: bad})


def _solo(pred, prompt, n):
    def step(toks):
        feed = np.zeros((1, CFG.max_len, 1), np.int64)
        feed[0, :len(toks), 0] = toks
        return int(np.argmax(pred.run({'tokens': feed})[0]
                             [0, len(toks) - 1]))
    toks, out = list(prompt), []
    for _ in range(n):
        t = step(toks)
        out.append(t)
        toks.append(t)
    return out


def test_identity_swap_midstream_is_bit_exact(lm_predictor):
    """request_swap re-installing the SAME weights mid-stream must be
    invisible: pause/swap/resume == undisturbed run, token for token,
    no matter which boundary the swap lands on."""
    from paddle_tpu.serving import ServingEngine
    solo = _solo(lm_predictor, [3, 1, 4], 10)
    dec = lm_predictor.prepare_decoding(slots=2)
    staged = dec.stage_weights(_current_weights(dec))
    with ServingEngine(dec) as eng:
        req = eng.submit([3, 1, 4], max_new_tokens=10)
        deadline = time.monotonic() + 60
        while len(req.tokens) < 2 and time.monotonic() < deadline:
            time.sleep(0.001)
        eng.request_swap(lambda: dec.install_weights(staged))
        assert req.result(120) == solo
        assert eng.stats()['weight_swaps'] == 1


def test_swap_switches_stream_at_one_boundary(lm_predictor):
    """A REAL weight change mid-stream: zeroing the lm_head makes every
    post-swap logit row constant, so every post-swap token is argmax
    tie-break 0. The stream must be a clean two-segment splice — an
    old-version prefix bit-exact with the undisturbed run, then the
    new-version suffix — with no blended step."""
    from paddle_tpu.serving import ServingEngine
    prompt, budget = [9, 9, 1, 5], 12
    solo = _solo(lm_predictor, prompt, budget)
    assert 0 not in solo, 'pick a prompt whose solo stream avoids 0'
    dec = lm_predictor.prepare_decoding(slots=2)
    cur = _current_weights(dec)
    head = [n for n in cur if 'lm_head' in n]
    assert head, sorted(cur)
    zeroed = dict(cur)
    for n in head:
        zeroed[n] = np.zeros_like(np.asarray(cur[n]))
    staged = dec.stage_weights(zeroed)
    restore = dec.stage_weights(cur)
    try:
        with ServingEngine(dec) as eng:
            req = eng.submit(prompt, max_new_tokens=budget)
            deadline = time.monotonic() + 60
            while len(req.tokens) < 3 and time.monotonic() < deadline:
                time.sleep(0.001)
            eng.request_swap(lambda: dec.install_weights(staged))
            out = req.result(120)
        assert len(out) == budget
        k = out.index(0) if 0 in out else budget
        assert k >= 3                       # swap never rewrote history
        assert out[:k] == solo[:k], 'pre-swap prefix diverged'
        assert all(t == 0 for t in out[k:]), \
            'post-swap tokens blended versions: %r' % (out[k:],)
    finally:
        dec.install_weights(restore)


def test_request_swap_runs_inline_when_engine_stopped(lm_predictor):
    from paddle_tpu.serving import ServingEngine
    dec = lm_predictor.prepare_decoding(slots=1)
    eng = ServingEngine(dec)                # never started
    ran = []
    assert eng.request_swap(lambda: ran.append(1) or 'ok') == 'ok'
    assert ran == [1]
    assert eng.stats()['weight_swaps'] == 1


def test_lmserver_stats_report_version_and_staleness(lm_predictor):
    from paddle_tpu.serving import LMServer
    dec = lm_predictor.prepare_decoding(slots=2)
    with LMServer(dec) as srv:
        stats = srv.stats()
        assert stats['param_version'] is None
        assert stats['staleness_rounds'] is None
        srv._subscriber = ParamSubscriber(['x:1'], dec)   # not started
        srv._subscriber.installed_version = 3
        srv._subscriber.published_version = 5
        srv._subscriber.refreshes = 3
        stats = srv.stats()
        assert stats['param_version'] == 3
        assert stats['staleness_rounds'] == 2
        assert stats['refreshes'] == 3
        assert stats['refresh_failures'] == 0


# ---------------------------------------------------------------------------
# acceptance: supervised trainer x pserver x serving cluster — decode
# tracks training with NO serving restart
# ---------------------------------------------------------------------------

def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(('127.0.0.1', 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


@pytest.mark.timeout(600)
def test_online_cluster_serving_tracks_training(tmp_path):
    """THE tentpole acceptance bar: 1 trainer x 2 pservers x 1 serving
    process under the Supervisor. After N sync rounds the serving
    process's installed params must DIGEST-MATCH the params the
    trainer pulled after round N (== the pserver fleet's version-N
    bytes), the installed version must read N, and the whole refresh
    history must have happened in ONE serving process (no restart:
    weight_swaps counted by the same engine that answered the warm-up
    generate)."""
    from paddle_tpu.distributed.supervisor import Supervisor
    steps, pservers = 3, 2
    eps = ','.join('127.0.0.1:%d' % p for p in _free_ports(pservers))
    workdir = str(tmp_path)
    base = dict(os.environ)
    base.pop('XLA_FLAGS', None)
    base.setdefault('JAX_PLATFORMS', 'cpu')
    base.update({'PS_ENDPOINTS': eps, 'PS_STEPS': str(steps),
                 'ON_DIR': workdir,
                 'FLAGS_online_poll_secs': '0.1'})
    sup = Supervisor(max_restarts=0, backoff=0.5, log_dir=workdir)
    for i in range(pservers):
        sup.add_role('pserver%d' % i, [sys.executable, _WORKER],
                     env=dict(base, ON_ROLE='pserver',
                              PS_PSERVER_ID=str(i)))
    sup.add_role('trainer', [sys.executable, _WORKER],
                 env=dict(base, ON_ROLE='trainer'))
    sup.add_role('serving', [sys.executable, _WORKER],
                 env=dict(base, ON_ROLE='serving'))
    sup.start()
    try:
        states = sup.wait(timeout=480)
        tout = sup.output('trainer')
        sout = sup.output('serving')
        assert all(s == 'done' for s in states.values()), \
            (states, tout[-4000:], sout[-4000:])
        assert all(r == 0 for r in sup.restarts.values()), sup.restarts
    finally:
        sup.stop()

    def result_of(out):
        lines = [ln for ln in out.splitlines()
                 if ln.startswith('RESULT ')]
        assert lines, out[-4000:]
        return json.loads(lines[-1][len('RESULT '):])

    trainer, serving = result_of(tout), result_of(sout)
    assert serving['installed_version'] == steps
    assert serving['refreshes'] >= 1
    assert serving['weight_swaps'] >= 1
    assert serving['refresh_failures'] == 0
    # every served param's installed bytes == the trainer's post-round-N
    # pulled bytes (== the pserver fleet's version-N shard bytes)
    assert serving['digests'], 'serving reported no params'
    for name, digest in serving['digests'].items():
        assert name in trainer['digests'], \
            'serving installed %r the trainer never trained' % name
        assert digest == trainer['digests'][name], \
            'param %r: serving bytes diverged from version-%d ' \
            'training bytes' % (name, steps)
    # decode ran on BOTH sides of the refresh in one process
    assert len(serving['tokens_before']) == len(
        serving['tokens_after']) == 8
    assert all(np.isfinite(trainer['losses']))
