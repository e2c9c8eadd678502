"""Fleet serving resilience: FleetRouter over ReplicaServer replicas.

The acceptance triangle (ISSUE: fleet serving resilience):

1. kill -9 one replica mid-stream -> every stream still completes and
   every token stream is BIT-exact vs the solo reference (failover
   re-prefills from the accumulated prefix; greedy decode makes the
   continuation identical);
2. a rolling param-version deploy across 2 live replicas drops zero
   streams and converges every replica to the new version's digests;
3. sustained overload trips admission control (typed OverloadError +
   fleet.shed) BEFORE the TTFT SLO rule breaches.

Plus the PR's satellites: Supervisor restart-budget reset after
healthy uptime, ServingEngine drain-timeout escalation and the
submit/cancel-during-drain races, and the ReplicaServer wire surface.

Replica processes for the kill test are real subprocesses
(tools/serve_replica.py) — SIGKILL needs a pid; everything else runs
in-process (ReplicaServer threads) to keep tier-1 wall-clock down.
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

import fleet_worker as fw
from paddle_tpu.distributed import wire
from paddle_tpu.integrity import crc32
from paddle_tpu.serving import (FleetRouter, LMServer, OverloadError,
                                ReplicaServer, ServingEngine)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SERVE_REPLICA = os.path.join(_ROOT, 'tools', 'serve_replica.py')
GEN = 12


def _free_ports(n):
    socks = []
    for _ in range(n):
        s = socket.socket()
        s.bind(('127.0.0.1', 0))
        socks.append(s)
    ports = [s.getsockname()[1] for s in socks]
    for s in socks:
        s.close()
    return ports


@pytest.fixture(scope='module')
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp('fleet_model'))
    fw.build_model(d)
    return d


@pytest.fixture(scope='module')
def ref_dec(model_dir):
    """In-process solo-decode reference over the same saved bytes."""
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    pred = AnalysisPredictor(AnalysisConfig(model_dir))
    return pred.prepare_decoding(slots=4)


def _launch_replicas(model_dir, n, slots=4, extra_env=None):
    """extra_env: {replica index: {env overrides}} — how a single
    replica gets a FaultPlan while its peers run clean."""
    eps, procs = [], []
    for i, port in enumerate(_free_ports(n)):
        ep = '127.0.0.1:%d' % port
        env = dict(os.environ, SERVE_MODEL_DIR=model_dir,
                   SERVE_ENDPOINT=ep, SERVE_SLOTS=str(slots),
                   SERVE_WORKERS='1')
        env.pop('XLA_FLAGS', None)
        env['JAX_PLATFORMS'] = 'cpu'    # the replica takes its platform
                                        # from its environment
        env.update((extra_env or {}).get(i, {}))
        procs.append(subprocess.Popen(
            [sys.executable, _SERVE_REPLICA], env=env,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL))
        eps.append(ep)
    return procs, eps


def _cleanup_replicas(procs, eps):
    for ep in eps:
        host, port = ep.rsplit(':', 1)
        try:
            with socket.create_connection((host, int(port)),
                                          timeout=2.0) as s:
                wire.write_msg(s, wire.COMPLETE, {'seq': 0})
                wire.read_msg(s)
        except OSError:
            pass
    for p in procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait(timeout=10)


class _InprocReplica(object):
    """ReplicaServer over an in-process LMServer, serving on a daemon
    thread — the wire surface without a subprocess."""

    def __init__(self, srv):
        self.rs = ReplicaServer(srv, '127.0.0.1:0')
        self.ep = '127.0.0.1:%d' % self.rs.port
        self._t = threading.Thread(target=self.rs.serve_forever,
                                   daemon=True)
        self._t.start()

    def stop(self):
        self.rs.shutdown()
        self._t.join(timeout=10)


# -- acceptance 1: replica kill-9, bit-exact failover ----------------------

@pytest.mark.timeout(600)
def test_fleet_replica_kill_failover_bit_exact(model_dir, ref_dec):
    procs, eps = _launch_replicas(model_dir, 2)
    router = FleetRouter(eps, poll_secs=0.005, probe_secs=0.05,
                         probe_fail_threshold=2)
    router.start()
    try:
        router.wait_healthy(timeout=240.0)
        work = fw.make_prompts(0, 30, GEN)
        reqs = [router.submit(p, max_new_tokens=GEN, session=s)
                for p, s in work]
        # kill the moment a replica is provably mid-stream: >= 2
        # active streams that already produced tokens
        victim_ep, deadline = None, time.monotonic() + 180
        while victim_ep is None and time.monotonic() < deadline:
            with router._mu:
                for ep, rep in router._reps.items():
                    if len([r for r in rep.active.values()
                            if r.tokens]) >= 2:
                        victim_ep = ep
                        break
            time.sleep(0.002)
        assert victim_ep, 'no replica reached 2 live streams'
        procs[eps.index(victim_ep)].kill()        # SIGKILL
        for r in reqs:
            assert r.wait(timeout=240.0), (r.id, r.state)
        assert router.stats()['failovers'] >= 1
        for r, (p, _s) in zip(reqs, work):
            assert r.state == 'DONE'
            assert r.result() == ref_dec.generate(p, GEN)
    finally:
        router.stop()
        _cleanup_replicas(procs, eps)


# -- acceptance 2: rolling deploy, zero drops, digest convergence ----------

@pytest.mark.timeout(600)
def test_fleet_rolling_deploy_zero_drop(model_dir):
    from paddle_tpu.distributed.param_service import ParameterService
    from paddle_tpu.distributed.rpc import PSClient, PSServer

    srv_a = LMServer(model_dir, slots=4)
    srv_b = LMServer(model_dir, slots=4)
    # the pserver hosts the model's own params from a test-owned dict:
    # mutating the dict + closing a round IS the new trained version
    params = {n: np.copy(np.asarray(
                  srv_a._decode._weight_scope.find_var(n)))
              for n in srv_a._decode.param_names()}
    svc = ParameterService(num_trainers=1, sync_mode=True,
                           get_param=lambda n: params[n],
                           run_round=lambda merged: None,
                           rpc_deadline=60.0,
                           param_names=sorted(params))
    ps = PSServer('127.0.0.1:0', svc)
    pst = threading.Thread(target=ps.serve_forever, daemon=True)
    pst.start()
    ps_eps = ['127.0.0.1:%d' % ps.port]
    srv_a.enable_refresh(ps_eps, subscriber_id=101, poll_secs=0.05,
                         paused=True)
    srv_b.enable_refresh(ps_eps, subscriber_id=102, poll_secs=0.05,
                         paused=True)
    ra, rb = _InprocReplica(srv_a), _InprocReplica(srv_b)
    router = FleetRouter([ra.ep, rb.ep], poll_secs=0.005,
                         probe_secs=0.05)
    reqs, stop_traffic = [], threading.Event()

    def traffic():
        rng = np.random.RandomState(7)
        while not stop_traffic.is_set():
            prompt = [int(t) for t in rng.randint(1, fw.CFG.vocab, 3)]
            reqs.append(router.submit(prompt, max_new_tokens=8))
            time.sleep(0.01)

    t = threading.Thread(target=traffic, daemon=True)
    try:
        router.start()
        router.wait_healthy(timeout=120.0)
        t.start()
        time.sleep(0.3)           # streams live on both replicas
        for n in list(params):
            params[n] = params[n] + np.float32(0.01)
        svc.on_send_var('r@GRAD', 0, np.zeros(1, 'f4'), seq=('t', 1))
        svc.on_batch_barrier(0, seq=('t', 2))     # publish version 1
        out = router.rolling_deploy(min_version=1)
        assert out == {ra.ep: 1, rb.ep: 1}
        time.sleep(0.2)           # post-deploy traffic too
        stop_traffic.set()
        t.join(timeout=10)
        assert reqs
        for r in reqs:
            assert r.wait(timeout=240.0), (r.id, r.state)
            assert r.state == 'DONE'          # zero drops
            assert len(r.tokens) == 8
        want = {n: crc32(wire._payload_of(
                    np.ascontiguousarray(params[n]))[1])
                for n in params}
        assert srv_a.param_digests() == want
        assert srv_b.param_digests() == want
        st = router.stats()
        assert st['deploys'] == 1
        assert st['shed'] == 0 and st['failed'] == 0
        assert {v['param_version']
                for v in st['replicas'].values()} == {1}
    finally:
        stop_traffic.set()
        t.join(timeout=10) if t.is_alive() else None
        router.stop()
        ra.stop()
        rb.stop()
        srv_a.close(drain=False)
        srv_b.close(drain=False)
        cli = PSClient('127.0.0.1:%d' % ps.port, trainer_id=0)
        cli.complete()
        cli.close()
        pst.join(timeout=10)


# -- acceptance 3: admission control sheds before the TTFT SLO -------------

@pytest.mark.timeout(600)
def test_fleet_admission_control_sheds_before_slo(model_dir):
    from paddle_tpu.obs import telemetry
    from paddle_tpu.obs.slo import SLORule

    srv = LMServer(model_dir, slots=2)
    rep = _InprocReplica(srv)
    router = FleetRouter(
        [rep.ep], poll_secs=0.005, probe_secs=0.02,
        shed_consecutive=1,
        admission_rules=[{'name': 'fleet_backlog',
                          'metric': 'fleet.queue_depth',
                          'kind': 'gauge_max', 'threshold': 6}])
    telemetry.enable()
    try:
        telemetry.reset()
        router.start()
        router.wait_healthy(timeout=120.0)
        rng = np.random.RandomState(9)
        accepted, sheds = [], 0
        for _ in range(60):
            prompt = [int(v) for v in rng.randint(1, fw.CFG.vocab, 3)]
            try:
                accepted.append(router.submit(prompt,
                                              max_new_tokens=6))
            except OverloadError:
                sheds += 1
            time.sleep(0.005)
        assert sheds > 0, router.stats()
        assert accepted
        st = router.stats()
        assert st['shed'] == sheds
        snap = telemetry.snapshot()
        assert snap['counters'].get('fleet.shed') == sheds
        # shedding protected the accepted streams: all complete, and
        # the TTFT SLO rule the shed pre-empts never breaches
        for r in accepted:
            assert r.wait(timeout=240.0), (r.id, r.state)
            assert r.state == 'DONE'
        rule = SLORule('ttft_slo', 'fleet.ttft', 'p99_max', 10.0)
        out = rule.evaluate(router.admission_snapshot())
        assert out is not None and not out[1], out
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()
        router.stop()
        rep.stop()
        srv.close(drain=False)


# -- satellite: ReplicaServer wire surface ---------------------------------

@pytest.mark.timeout(600)
def test_replica_server_wire_roundtrip(model_dir, ref_dec):
    srv = LMServer(model_dir, slots=2)
    rep = _InprocReplica(srv)
    sock = socket.create_connection(('127.0.0.1', rep.rs.port),
                                    timeout=10)
    seq = [0]

    def call(mt, meta=None, value=None):
        seq[0] += 1
        m = dict(meta or {}, seq=seq[0])
        wire.write_msg(sock, mt, m, value)
        rt, rmeta, _ = wire.read_msg(sock)
        assert rmeta['seq'] == seq[0]     # every reply echoes the seq
        return rt, rmeta

    try:
        rt, h = call(wire.SRV_HEALTH, {})
        assert rt == wire.REPLY_OK
        assert h['capacity'] == 2
        assert h['max_len'] == fw.CFG.max_len
        assert h['draining'] is False
        rt, h2 = call(wire.SRV_HEALTH, {'digests': True})
        assert h2['digests'] == srv.param_digests()

        prompt = [3, 1, 4]
        rt, _m = call(wire.SRV_SUBMIT, {'rid': 'r1', 'mnt': 6},
                      np.asarray(prompt, np.int64))
        assert rt == wire.REPLY_OK
        deadline = time.monotonic() + 120
        while True:
            rt, pr = call(wire.SRV_POLL, {'rids': ['r1', 'ghost']})
            assert pr['streams']['ghost'] == {'state': 'UNKNOWN',
                                              'tokens': []}
            if pr['streams']['r1']['state'] == 'DONE':
                break
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert pr['streams']['r1']['tokens'] == \
            ref_dec.generate(prompt, 6)

        # drain fence: submits rejected RETRYABLY while draining
        rt, _m = call(wire.SRV_DRAIN, {'on': True})
        assert rt == wire.REPLY_OK
        rt, err = call(wire.SRV_SUBMIT, {'rid': 'r2', 'mnt': 2},
                       np.asarray([5], np.int64))
        assert rt == wire.REPLY_ERR and err['retryable'] is True
        rt, _m = call(wire.SRV_DRAIN, {'on': False})

        # cancel mid-stream: terminal state, partial tokens kept
        rt, _m = call(wire.SRV_SUBMIT, {'rid': 'r3', 'mnt': 10 ** 6},
                      np.asarray([2, 6], np.int64))
        assert rt == wire.REPLY_OK
        while True:
            rt, pr = call(wire.SRV_POLL, {'rids': ['r3']})
            if pr['streams']['r3']['tokens']:
                break
            assert time.monotonic() < deadline
            time.sleep(0.01)
        rt, _m = call(wire.SRV_CANCEL, {'rid': 'r3'})
        assert rt == wire.REPLY_OK
        while pr['streams']['r3']['state'] != 'CANCELLED':
            rt, pr = call(wire.SRV_POLL, {'rids': ['r3']})
            assert time.monotonic() < deadline
            time.sleep(0.01)

        # no subscriber attached: refresh is a NON-retryable error
        rt, err = call(wire.SRV_REFRESH, {})
        assert rt == wire.REPLY_ERR and err['retryable'] is False
        # a message type the replica does not serve
        rt, err = call(wire.GET_VAR, {'name': 'w'})
        assert rt == wire.REPLY_ERR and err['retryable'] is False
    finally:
        sock.close()
        rep.stop()
        srv.close(drain=False)


# -- satellite: SRV_SUBMIT prio/deadline meta across both encodings --------

@pytest.mark.timeout(600)
def test_replica_submit_prio_deadline_meta_roundtrip(model_dir):
    """priority + deadline_ms must survive the SRV_SUBMIT hop under
    BOTH meta encodings (JSON and binary-meta v3), and a peer that
    predates the keys (meta simply lacks them) must decode to the
    defaults — tier 0, no deadline — not an error."""
    srv = LMServer(model_dir, slots=2)
    seen = []
    orig_submit = srv.submit

    def spy(prompt, **kw):
        seen.append(dict(kw))
        return orig_submit(prompt, **kw)

    srv.submit = spy
    rep = _InprocReplica(srv)
    try:
        for bmeta in (False, True):
            sock = socket.create_connection(
                ('127.0.0.1', rep.rs.port), timeout=10)
            if bmeta:
                wire._mark_peer_bmeta(sock)    # force bmeta v3 framing
            seq = [0]

            def call(mt, meta=None, value=None, _sock=sock, _seq=seq):
                _seq[0] += 1
                m = dict(meta or {}, seq=_seq[0])
                wire.write_msg(_sock, mt, m, value)
                rt, rmeta, _ = wire.read_msg(_sock)
                assert rmeta['seq'] == _seq[0]
                return rt, rmeta

            try:
                tag = 'b' if bmeta else 'j'
                rt, _m = call(wire.SRV_SUBMIT,
                              {'rid': tag + '1', 'mnt': 4, 'prio': 2,
                               'deadline_ms': 60000.0},
                              np.asarray([3, 1, 4], np.int64))
                assert rt == wire.REPLY_OK
                assert seen[-1]['priority'] == 2
                assert seen[-1]['deadline_ms'] == pytest.approx(60000.0)

                # old-peer meta: absent keys mean defaults, not errors
                rt, _m = call(wire.SRV_SUBMIT,
                              {'rid': tag + '2', 'mnt': 2},
                              np.asarray([5], np.int64))
                assert rt == wire.REPLY_OK
                assert seen[-1]['priority'] == 0
                assert seen[-1]['deadline_ms'] is None

                # a near-spent deadline expires inside the engine and
                # the typed failure class crosses SRV_POLL back out
                rt, _m = call(wire.SRV_SUBMIT,
                              {'rid': tag + '3', 'mnt': 10 ** 6,
                               'deadline_ms': 1.0},
                              np.asarray([2, 6], np.int64))
                assert rt == wire.REPLY_OK
                deadline = time.monotonic() + 120
                while True:
                    rt, pr = call(wire.SRV_POLL,
                                  {'rids': [tag + '3']})
                    st = pr['streams'][tag + '3']
                    if st['state'] in ('DONE', 'FAILED', 'CANCELLED'):
                        break
                    assert time.monotonic() < deadline
                    time.sleep(0.01)
                assert st['state'] == 'FAILED'
                assert 'DeadlineExceeded' in st['error']
            finally:
                sock.close()
    finally:
        rep.stop()
        srv.close(drain=False)


# -- satellite: progress watchdog gray-marks a stalled replica -------------

@pytest.mark.timeout(600)
def test_fleet_watchdog_fails_over_stalled_replica_bit_exact(
        model_dir, ref_dec):
    """Gray failure, not fail-stop: replica0's data path freezes for
    25s mid-burst (FaultPlan stall on its 2nd inbound SRV_POLL) while
    its health probes keep answering. The progress watchdog must
    gray-mark it, interrupt the wedged connection, and fail its streams
    over — every stream completing bit-exact vs the solo reference."""
    plan = json.dumps({'rules': [{'when': 'recv', 'type': 'SRV_POLL',
                                  'nth': 2, 'action': 'stall',
                                  'secs': 25.0}]})
    from paddle_tpu import flags
    procs, eps = _launch_replicas(
        model_dir, 2, extra_env={0: {'FLAGS_fault_plan': plan}})
    router = None
    old_timeout = flags.get_flag('fleet_progress_timeout_secs')
    try:
        work = fw.make_prompts(3, 8, GEN)
        # warm both replicas over direct connections (SRV_SUBMIT +
        # SRV_HEALTH only): the cold jit compile happens before the
        # watchdog is armed, and the stall rule's SRV_POLL count
        # survives untouched into the measured burst
        for ep in eps:
            fw._warm_replica(ep, work[0][0], GEN)
        flags.set_flags({'FLAGS_fleet_progress_timeout_secs': 2.5})
        router = FleetRouter(eps, poll_secs=0.005, probe_secs=0.05,
                             probe_fail_threshold=2)
        router.start()
        router.wait_healthy(timeout=240.0)
        reqs = [router.submit(p, max_new_tokens=GEN, session=s)
                for p, s in work]
        for r in reqs:
            assert r.wait(timeout=240.0), (r.id, r.state)
        st = router.stats()
        assert st['gray_marks'] >= 1, st
        for r, (p, _s) in zip(reqs, work):
            assert r.state == 'DONE'
            assert np.array_equal(r.result(), ref_dec.generate(p, GEN))
    finally:
        flags.set_flags(
            {'FLAGS_fleet_progress_timeout_secs': old_timeout})
        if router is not None:
            router.stop()
        _cleanup_replicas(procs, eps)


# -- satellite: supervisor restart-budget reset ----------------------------

def test_supervisor_budget_reset_after_healthy_uptime(tmp_path):
    from paddle_tpu.distributed.supervisor import Supervisor
    script = 'import time, sys; time.sleep(0.7); sys.exit(1)'
    sup = Supervisor(max_restarts=1, backoff=0.05, healthy_secs=0.5,
                     log_dir=str(tmp_path))
    sup.add_role('r', [sys.executable, '-c', script])
    sup.start()
    try:
        # budget is 1, but every crash follows >= healthy_secs of
        # uptime, so the budget keeps resetting and the LIFETIME count
        # climbs past it
        deadline = time.monotonic() + 60
        while sup.restarts['r'] < 2 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sup.restarts['r'] >= 2
        assert any('budget reset' in e[2] for e in sup.events)
        assert sup.states()['r'] != 'failed'
    finally:
        sup.stop()


def test_supervisor_budget_still_bounds_crash_loops(tmp_path):
    from paddle_tpu.distributed.supervisor import Supervisor
    sup = Supervisor(max_restarts=1, backoff=0.05, healthy_secs=0.5,
                     log_dir=str(tmp_path))
    sup.add_role('r', [sys.executable, '-c',
                       'import sys; sys.exit(1)'])
    sup.start()
    try:
        states = sup.wait(timeout=60)
        assert states['r'] == 'failed'
        assert sup.restarts['r'] == 1     # instant crashes: no reset
        assert not any('budget reset' in e[2] for e in sup.events)
    finally:
        sup.stop()


# -- satellite: engine drain timeout + drain races -------------------------

@pytest.fixture(scope='module')
def long_model_dir(tmp_path_factory):
    """A window that a 10**9-token request cannot outgrow before the
    drains below time out (about 10 s of decode steps on this CPU): a
    stream past its window ends FAILED, not CANCELLED."""
    from paddle_tpu.models.transformer import TransformerConfig
    d = str(tmp_path_factory.mktemp('fleet_long_model'))
    fw.build_model(d, TransformerConfig(
        vocab=64, dim=32, heads=2, layers=1, ffn=64, max_len=8192,
        use_tp=False, use_sp=False))
    return d


@pytest.fixture()
def engine_dec(long_model_dir):
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    pred = AnalysisPredictor(AnalysisConfig(long_model_dir))
    return pred.prepare_decoding(slots=2)


def _wait_tokens(req, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not req.tokens:
        assert time.monotonic() < deadline, req.state
        time.sleep(0.005)


@pytest.mark.timeout(600)
def test_engine_drain_timeout_escalates_to_cancel(engine_dec):
    eng = ServingEngine(engine_dec).start()
    req = eng.submit([1, 2, 3], max_new_tokens=10 ** 9)
    _wait_tokens(req)
    t0 = time.monotonic()
    clean = eng.stop(drain=True, timeout=0.5)
    took = time.monotonic() - t0
    assert clean is False          # the escalation fired
    assert took < 30.0             # ... instead of hanging forever
    assert req.state == 'CANCELLED'
    assert req.tokens              # partial stream stays readable


@pytest.mark.timeout(600)
def test_engine_submit_during_drain_rejected(engine_dec):
    eng = ServingEngine(engine_dec).start()
    req = eng.submit([1, 2], max_new_tokens=10 ** 9)
    _wait_tokens(req)
    stopper = threading.Thread(
        target=lambda: eng.stop(drain=True, timeout=2.0), daemon=True)
    stopper.start()
    time.sleep(0.2)                # stop() flipped _accepting first
    with pytest.raises(RuntimeError, match='draining'):
        eng.submit([3], max_new_tokens=2)
    stopper.join(timeout=60.0)
    assert not stopper.is_alive()
    assert req.state == 'CANCELLED'


@pytest.mark.timeout(600)
def test_engine_cancel_during_drain_completes_promptly(engine_dec):
    eng = ServingEngine(engine_dec).start()
    req = eng.submit([1, 2], max_new_tokens=10 ** 9)
    _wait_tokens(req)
    result = {}

    def stopper():
        result['clean'] = eng.stop(drain=True, timeout=120.0)

    t = threading.Thread(target=stopper, daemon=True)
    t.start()
    time.sleep(0.2)
    eng.cancel(req)                # unblocks the drain immediately
    t.join(timeout=60.0)
    assert not t.is_alive()
    assert result['clean'] is True
    assert req.state == 'CANCELLED'
    assert req.tokens
