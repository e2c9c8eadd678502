"""Preempt-first capacity: SLO-tiered preemption with host-RAM page
swap and bit-exact resume (serving/preempt.py + engine tier queues).

The contract under test (ISSUE 16 acceptance):
- preempt -> swap -> resume and preempt -> drop -> re-prefill ->
  resume both yield token streams np.array_equal to the unpreempted
  reference (greedy determinism + exact float32 page round-trips)
- a dry FLAGS_serving_swap_host_mb budget degrades swap to re-prefill
  instead of growing host memory — still bit-exact
- speculative decoding composes: a resumed slot falls back to plain
  decode (draft-dead) when its draft cannot re-prefill, and emitted
  tokens never change either way
- PagePool.check() invariants hold through seeded alloc/free/
  save_pages/restore_pages churn, and restored page content equals
  what was saved
- a preempted stream that then loses its replica fails over and still
  finishes bit-exact (the fleet carries priority end-to-end)
- tier queues: higher tiers dequeue first, queue-full admission
  rejects only priority <= 0, and a front-requeue re-enters its OWN
  tier ahead of that tier's waiting admissions
"""
import time

import numpy as np
import pytest

import fleet_worker as fw
from paddle_tpu.flags import set_flags
from paddle_tpu.serving import (FleetRouter, HostSwapBudget, PagePool,
                                ServingEngine)
from paddle_tpu.serving.engine import _Lane, Request
from paddle_tpu.serving.paging import CacheExhaustedError
from paddle_tpu.serving.preempt import pick_victim, preempt_policy

GEN = 8
PA = [1, 2, 3, 4, 5, 6, 7, 8]
PB = [8, 7, 6, 5, 4, 3, 2, 1]


@pytest.fixture(scope='module')
def model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp('preempt_model'))
    fw.build_model(d)
    return d


@pytest.fixture(scope='module')
def predictor(model_dir):
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    return AnalysisPredictor(AnalysisConfig(model_dir))


@pytest.fixture(scope='module')
def ref_dec(predictor):
    """Solo reference over the same saved bytes: the same stream alone
    on a pool of its own that fits it (whatever FLAGS_serving_kv_pages
    a test sets for the server under test)."""
    return predictor.prepare_decoding(slots=1, page_tokens=4, kv_pages=8)


@pytest.fixture()
def policy_flags():
    """Restore the preemption flags a test mutates."""
    yield
    set_flags({'FLAGS_serving_preempt_policy': 'swap',
               'FLAGS_serving_swap_host_mb': 64})


def _tight_engine(predictor):
    """2 slots over a pool too small for two full streams: decoding
    both PA and PB to GEN tokens is guaranteed to exhaust it."""
    dec = predictor.prepare_decoding(slots=2, page_tokens=4,
                                     kv_pages=6,
                                     prefill_chunk=fw.CFG.max_len)
    return dec, ServingEngine(dec)


def _wait_tokens(req, timeout=120.0):
    deadline = time.monotonic() + timeout
    while not req.tokens:
        assert time.monotonic() < deadline, req.state
        time.sleep(0.005)


# --------------------------------------------------------------------------
# policy units: victim choice, budget, flag validation
# --------------------------------------------------------------------------

def test_pick_victim_lowest_tier_then_longest_idle():
    low_new = _Lane(Request([1], 4, None, priority=0), 5, 1)
    low_old = _Lane(Request([1], 4, None, priority=0), 5, 1)
    high_oldest = _Lane(Request([1], 4, None, priority=2), 5, 1)
    low_new.last_active, low_old.last_active = 100.0, 50.0
    high_oldest.last_active = 1.0
    lanes = {0: low_new, 1: high_oldest, 2: low_old}
    assert pick_victim(lanes) == 2        # tier beats idleness
    assert pick_victim(lanes, below=2) == 2
    assert pick_victim(lanes, below=0) is None   # nothing strictly under
    low_old.ready = False                 # mid-prefill: not a candidate
    assert pick_victim(lanes) == 0
    low_new.ready = high_oldest.ready = False
    assert pick_victim(lanes) is None
    assert pick_victim({}) is None


def test_host_swap_budget_reserve_all_or_nothing():
    b = HostSwapBudget(limit_mb=1)
    assert b.limit_bytes == 1 << 20
    assert b.reserve(1 << 19) and b.used_bytes == 1 << 19
    assert not b.reserve((1 << 19) + 1)   # would exceed: nothing taken
    assert b.used_bytes == 1 << 19
    assert b.reserve(1 << 19)             # exact fit
    b.release(1 << 20)
    assert b.used_bytes == 0
    assert not HostSwapBudget(limit_mb=0).reserve(1)


def test_preempt_policy_flag_validated(policy_flags):
    assert preempt_policy() == 'swap'
    set_flags({'FLAGS_serving_preempt_policy': 'bogus'})
    with pytest.raises(ValueError, match='serving_preempt_policy'):
        preempt_policy()


# --------------------------------------------------------------------------
# tier queues: ordering + low-tier-only admission bound
# --------------------------------------------------------------------------

def test_tier_queues_order_and_low_tier_only_rejection(predictor):
    dec = predictor.prepare_decoding(slots=2)
    eng = ServingEngine(dec, max_queue=2)     # never started: pure queue
    low_a = eng.submit([1], 2)
    high = eng.submit([2], 2, priority=5)
    # queue is at max_queue, but only the lowest tier is bounded
    mid = eng.submit([3], 2, priority=1)
    with pytest.raises(RuntimeError, match='queue full'):
        eng.submit([4], 2)
    # a front-requeue (exhaustion victim / preempted stream) re-enters
    # its OWN tier's front — ahead of low_a, behind every higher tier
    victim = Request([5], 2, None, priority=0)
    with eng._cond:
        eng._push_locked(victim, front=True)
    order = [eng._pop_next() for _ in range(4)]
    assert order == [high, mid, victim, low_a]
    assert eng._pop_next() is None


# --------------------------------------------------------------------------
# allocator: save/restore churn keeps PagePool invariants + content
# --------------------------------------------------------------------------

def test_pool_invariants_after_swap_restore_churn():
    rng = np.random.RandomState(23)
    pool = PagePool(17, 4)
    arr = rng.rand(17, 4, 2, 2).astype('f4')  # one backing pool array
    held, swapped = [], []                    # page ids / host snapshots
    for _ in range(800):
        r = rng.rand()
        if r < 0.40:
            try:
                p = pool.alloc()
            except CacheExhaustedError:
                assert pool.pages_free == 0
            else:
                arr[p] = rng.rand(4, 2, 2)
                held.append(p)
        elif r < 0.60 and held:
            # swap out: gather to host, then give the pages back
            k = int(rng.randint(1, min(3, len(held)) + 1))
            ids = [held.pop(int(rng.randint(len(held))))
                   for _ in range(k)]
            data = pool.save_pages([arr], ids)
            assert np.array_equal(data[0], arr[np.asarray(ids)])
            for p in ids:
                pool.unref(p)
            swapped.append(data)
        elif r < 0.80 and swapped:
            data = swapped.pop(int(rng.randint(len(swapped))))
            try:
                ids, (arr,) = pool.restore_pages([arr], data)
            except CacheExhaustedError:
                swapped.append(data)          # all-or-nothing: retry later
            else:
                assert np.array_equal(arr[np.asarray(ids)], data[0])
                held.extend(ids)
        elif held:
            pool.unref(held.pop(int(rng.randint(len(held)))))
        pool.check()
    # saving a freed or null page is a caller bug, not a silent gather
    if held:
        ghost = held.pop()
        pool.unref(ghost)
        with pytest.raises(ValueError, match='dead/null'):
            pool.save_pages([arr], [ghost])
        pool.check()
    with pytest.raises(ValueError, match='dead/null'):
        pool.save_pages([arr], [0])
    # drain: everything restores (free what blocks it), content exact
    for p in held:
        pool.unref(p)
    for data in swapped:
        ids, (arr,) = pool.restore_pages([arr], data)
        assert np.array_equal(arr[np.asarray(ids)], data[0])
        for p in ids:
            pool.unref(p)
    pool.check()
    assert pool.pages_in_use == 0


# --------------------------------------------------------------------------
# engine: preempt -> resume is bit-exact on every policy path
# --------------------------------------------------------------------------

def _run_contended(eng, ref_a, ref_b):
    """Low-tier PA first; once it is provably decoding, high-tier PB —
    the pool cannot hold both, so PB's growth preempts PA."""
    eng.start()
    try:
        ra = eng.submit(PA, max_new_tokens=GEN, priority=0)
        _wait_tokens(ra)
        rb = eng.submit(PB, max_new_tokens=GEN, priority=1)
        out_b = rb.result(240)
        out_a = ra.result(240)
        st = eng.stats()
    finally:
        eng.stop()
    assert np.array_equal(out_a, ref_a), (out_a, ref_a)
    assert np.array_equal(out_b, ref_b), (out_b, ref_b)
    return st


@pytest.mark.timeout(600)
def test_preempt_swap_resume_bit_exact(predictor, ref_dec,
                                       policy_flags):
    from paddle_tpu.obs import telemetry, trace
    ref_a, ref_b = ref_dec.generate(PA, GEN), ref_dec.generate(PB, GEN)
    _dec, eng = _tight_engine(predictor)
    telemetry.enable()
    try:
        telemetry.reset()
        trace.clear()
        st = _run_contended(eng, ref_a, ref_b)
        snap = telemetry.snapshot()
        spans = trace.spans()
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()
        trace.clear()
    assert st['preemptions'] >= 1 and st['resumes'] >= 1
    assert st['preempted_streams'] == 0   # everyone came back
    assert st['swap_host_bytes'] == 0     # ... and gave its budget back
    assert snap['counters']['serving.preemptions'] == st['preemptions']
    assert snap['counters']['serving.swapped_pages'] >= 1
    assert snap['counters']['serving.swap_bytes'] >= 1
    assert snap['hists']['serving.resume_latency']['count'] \
        == st['resumes']
    # the preempted stream's spans: one serve.requeue per resumption
    # beside its three phases, under its id; serve.queue stays the
    # FIRST wait, so the requeues lie after it
    requeues = [s for s in spans if s['name'] == 'serve.requeue']
    assert len(requeues) == st['resumes']
    victim = requeues[0]['sid']
    mine = {s['name']: s for s in spans
            if s['kind'] == 'request' and s['sid'] == victim}
    assert set(mine) == {'serve.queue', 'serve.prefill', 'serve.decode',
                         'serve.requeue'}
    assert mine['serve.decode']['preemptions'] >= 1
    assert mine['serve.decode']['state'] == 'DONE'
    assert all(r['t0'] >= mine['serve.queue']['t1'] and r['t1'] > r['t0']
               for r in requeues)


@pytest.mark.timeout(600)
@pytest.mark.parametrize('flags', [
    # explicit drop-and-re-prefill policy
    {'FLAGS_serving_preempt_policy': 'reprefill'},
    # swap policy with a dry host budget degrades to re-prefill
    {'FLAGS_serving_preempt_policy': 'swap',
     'FLAGS_serving_swap_host_mb': 0},
], ids=['reprefill', 'swap_budget_dry'])
def test_preempt_reprefill_resume_bit_exact(predictor, ref_dec,
                                            policy_flags, flags):
    set_flags(flags)
    ref_a, ref_b = ref_dec.generate(PA, GEN), ref_dec.generate(PB, GEN)
    _dec, eng = _tight_engine(predictor)
    st = _run_contended(eng, ref_a, ref_b)
    assert st['preemptions'] >= 1 and st['resumes'] >= 1
    assert st['swap_host_bytes'] == 0     # nothing ever swapped


@pytest.mark.timeout(600)
def test_preempt_policy_off_keeps_legacy_shed(predictor, ref_dec,
                                              policy_flags):
    set_flags({'FLAGS_serving_preempt_policy': 'off'})
    _dec, eng = _tight_engine(predictor)
    eng.start()
    try:
        ra = eng.submit(PA, max_new_tokens=GEN)
        _wait_tokens(ra)
        rb = eng.submit(PB, max_new_tokens=GEN)
        ra.wait(240)
        rb.wait(240)
        st = eng.stats()
    finally:
        eng.stop()
    # the old typed-shed behavior: one stream fails CacheExhausted
    # (the fleet layer retries it elsewhere), nothing is preempted
    states = sorted([ra.state, rb.state])
    assert states == ['DONE', 'FAILED']
    failed = ra if ra.state == 'FAILED' else rb
    assert 'CacheExhausted' in failed.error
    assert st['preemptions'] == 0


@pytest.mark.timeout(600)
@pytest.mark.parametrize('policy', ['swap', 'reprefill', 'off'])
def test_exhaustion_at_a_deferred_step_accepts_the_one_in_flight(
        predictor, ref_dec, policy_flags, policy):
    """The pipelined loop meets CacheExhaustedError with a step in
    flight: that step is collected and accepted before a victim is
    saved, released or failed, and every stream that finishes is the
    reference's."""
    from paddle_tpu.obs import telemetry
    set_flags({'FLAGS_serving_preempt_policy': policy})
    pb = PB[:4]
    ref_a, ref_b = ref_dec.generate(PA, GEN), ref_dec.generate(pb, GEN)
    dec, eng = _tight_engine(predictor)
    seen = []                 # (what, was a step in flight)
    later = []
    step = dec.decode_step

    def decode_step(*a, **kw):
        # PA holds three of the five pages from its first step on; a
        # prompt of one page joins at PA's second step and takes the
        # other two (its own first step opens a page); PA's fifth step
        # (position 12) finds none, with the step before it in flight
        seen.append(('step', dec.in_flight))
        if len([ev for ev in seen if ev[0] == 'step']) == 2:
            later.append(eng.submit(pb, max_new_tokens=GEN, priority=1))
        try:
            return step(*a, **kw)
        except CacheExhaustedError:
            seen.append(('exhausted', dec.in_flight))
            raise

    def noting(what, call):
        def noted(slot):
            seen.append((what, dec.in_flight))
            return call(slot)
        return noted
    dec.decode_step = decode_step
    dec.save_stream = noting('save', dec.save_stream)
    dec.release = noting('release', dec.release)
    telemetry.enable()
    try:
        telemetry.reset()
        eng.start()
        try:
            ra = eng.submit(PA, max_new_tokens=GEN, priority=0)
            _wait_tokens(ra)
            assert ra.wait(240)
            rb, = later
            assert rb.wait(240)
            st = eng.stats()
        finally:
            eng.stop()
        counters = telemetry.snapshot()['counters']
    finally:
        telemetry.disable(final_flush=False)
        telemetry.reset()
    seen = [ev for ev in seen if ev[0] != 'step']
    assert counters['serving.decode_steps_overlapped'] >= 1
    hits = [i for i, ev in enumerate(seen) if ev == ('exhausted', True)]
    assert hits, seen         # it did happen with a step in flight
    for i in hits:
        # what follows the refusal sees the predictor whole again
        assert seen[i + 1][0] in ('save', 'release') \
            and seen[i + 1][1] is False, seen
    if policy == 'off':
        assert sorted([ra.state, rb.state]) == ['DONE', 'FAILED']
        assert st['preemptions'] == 0
    else:
        assert st['preemptions'] >= 1 and st['resumes'] >= 1
    for req, ref in ((ra, ref_a), (rb, ref_b)):
        if req.state == 'DONE':
            assert np.array_equal(req.tokens, ref), (req.tokens, ref)
        else:
            assert 'CacheExhausted' in req.error
            assert req.tokens == list(ref[:len(req.tokens)])
    assert not dec.in_flight and not dec.slot_tokens()


@pytest.mark.timeout(600)
def test_speculative_preemption_bit_exact(predictor, ref_dec,
                                          policy_flags):
    ref_a, ref_b = ref_dec.generate(PA, GEN), ref_dec.generate(PB, GEN)
    dec = predictor.prepare_decoding(slots=2, speculative=True,
                                     spec_k=3, page_tokens=4,
                                     kv_pages=6,
                                     prefill_chunk=fw.CFG.max_len)
    st = _run_contended(ref_a=ref_a, ref_b=ref_b,
                        eng=ServingEngine(dec))
    assert st['preemptions'] >= 1


# --------------------------------------------------------------------------
# fleet: a preempted stream survives losing its replica, bit-exact
# --------------------------------------------------------------------------

def _launch_paged_replicas(model_dir, n):
    """Subprocess replicas (tools/serve_replica.py) with a pool too
    tight for their slot count — SIGKILL needs a pid, and decode
    pressure needs a small SERVE_KV_PAGES."""
    import os
    import socket
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    eps, procs = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(('127.0.0.1', 0))
        ep = '127.0.0.1:%d' % s.getsockname()[1]
        s.close()
        env = dict(os.environ, SERVE_MODEL_DIR=model_dir,
                   SERVE_ENDPOINT=ep, SERVE_SLOTS='2',
                   SERVE_WORKERS='1',
                   SERVE_PAGE_TOKENS='4', SERVE_KV_PAGES='6',
                   SERVE_PREFILL_CHUNK=str(fw.CFG.max_len))
        env.pop('XLA_FLAGS', None)
        env['JAX_PLATFORMS'] = 'cpu'
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join(root, 'tools',
                                          'serve_replica.py')],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL))
        eps.append(ep)
    return procs, eps


@pytest.mark.timeout(600)
def test_preempted_stream_survives_replica_failover(model_dir,
                                                    ref_dec):
    from paddle_tpu.distributed import wire as _wire
    import socket
    procs, eps = _launch_paged_replicas(model_dir, 2)
    router = FleetRouter(eps, poll_secs=0.005, probe_secs=0.05,
                         probe_fail_threshold=2)
    router.start()
    try:
        router.wait_healthy(timeout=240.0)
        work = fw.make_prompts(3, 24, GEN)
        # mixed tiers: every third stream is high-priority — the rest
        # are the preemption victims that keep both pools churning
        reqs = [router.submit(p, max_new_tokens=GEN, session=s,
                              priority=1 if i % 3 == 0 else 0)
                for i, (p, s) in enumerate(work)]
        # wait until a replica has actually preempted (the priority
        # rode SRV_SUBMIT; the count rides SRV_HEALTH into stats) ...
        deadline = time.monotonic() + 240
        while router.stats()['preemptions'] < 1:
            assert time.monotonic() < deadline, 'no preemption happened'
            time.sleep(0.005)
        # ... then SIGKILL a replica that is provably mid-stream, so
        # its preempted + live streams all fail over to the survivor
        victim_ep = None
        while victim_ep is None and time.monotonic() < deadline:
            with router._mu:
                for ep, rep in router._reps.items():
                    if any(r.tokens for r in rep.active.values()):
                        victim_ep = ep
                        break
            time.sleep(0.002)
        assert victim_ep, 'no replica was mid-stream'
        procs[eps.index(victim_ep)].kill()
        for r in reqs:
            assert r.wait(timeout=240.0), (r.id, r.state)
            assert r.state == 'DONE'
        for r, (p, _s) in zip(reqs, work):
            assert np.array_equal(r.result(), ref_dec.generate(p, GEN))
        st = router.stats()
        assert st['failovers'] >= 1
        assert st['preemptions'] >= 1     # health ingestion saw them
    finally:
        router.stop()
        for ep in eps:
            host, port = ep.rsplit(':', 1)
            try:
                with socket.create_connection((host, int(port)),
                                              timeout=2.0) as s:
                    _wire.write_msg(s, _wire.COMPLETE, {'seq': 0})
                    _wire.read_msg(s)
            except OSError:
                pass
        for p in procs:
            try:
                p.wait(timeout=30)
            except Exception:
                p.kill()
                p.wait(timeout=10)
