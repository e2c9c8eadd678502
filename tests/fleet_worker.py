"""Subprocess worker for the fleet serving tests and
tools/chaos_sweep.py --fleet.

Two roles over one tiny transformer LM (replica processes themselves
run tools/serve_replica.py — this file covers what sits around them):

- build: construct the seeded model once and save_inference_model it
  into FLEET_MODEL_DIR — every replica (and the in-process reference
  predictor) loads the same bytes, so greedy streams are comparable
  across processes and runs.

- driver: a FleetRouter over FLEET_REPLICAS; submits FLEET_STREAMS
  seeded prompts (sessions cycling over a small pool), waits for every
  stream, then prints 'RESULT <json>' with the token streams, states
  and failover count, and finally COMPLETEs each replica so it exits
  0. The driver is itself a chaos victim: a restarted driver re-runs
  the whole workload from scratch (same seed -> same prompts -> same
  greedy streams), so the LAST RESULT line in its log is always a
  full, comparable answer.

- overload: the chaos_sweep --overload driver — a seeded mixed-tier
  burst of FLEET_STREAMS prompts (every 3rd priority 1, the rest tier
  0) submitted all at once against a fleet whose paged replicas are
  sized well below the burst, so the replicas MUST preempt low-tier
  streams to finish. OverloadError is tolerated (and counted) only
  for tier 0; every completed stream is checked bit-exact against an
  in-process solo-decode reference over the same FLEET_MODEL_DIR
  bytes, so the RESULT json carries verdict-ready counts
  (high_sheds / high_bad / low_failed / mismatches / preemptions)
  instead of raw streams.

- disagg: the chaos_sweep --disagg driver — a FleetRouter over two
  PAGED decode replicas plus a prefill tier (FLEET_PREFILL), running
  a seeded mixed burst where every other stream carries one shared
  8-token system prefix (two full 4-token pages — the shippable
  chain). Long streams dispatch with meta['prefill_from'] and the
  decode replicas pull pages over SRV_PAGE_FETCH; the sweep kills or
  gray-stalls the prefill replica mid-ship, and acceptance is every
  stream DONE and bit-exact (np.array_equal) against the in-process
  solo reference with failovers + local_reprefills >= 1 — a dead or
  frozen prefill tier must cost latency only, never tokens.

- grayfail: the chaos_sweep --grayfail driver — replica 0 carries a
  seeded ``stall`` FaultPlan (alive-but-frozen: health keeps passing,
  its data connection stops mid-stream), and the router runs with the
  gray-failure watchdog armed (FLAGS_fleet_progress_timeout_secs).
  Every replica is jit-warmed FIRST over a direct wire connection
  that completion-checks via SRV_HEALTH — never SRV_POLL — so warmup
  can neither trip the cold-compile watchdog false positive nor
  consume the stall rule's SRV_POLL trigger count. Every 3rd stream
  is priority 1 with a generous deadline_ms; acceptance is every
  stream bit-exact (np.array_equal) against the in-process solo
  reference, gray_marks >= 1 once the stall fired, and ZERO high-tier
  deadline violations.
"""
import json
import os
import socket
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from paddle_tpu.models.transformer import TransformerConfig  # noqa: E402

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=16, use_tp=False, use_sp=False)
SEED = 11
SESSIONS = 4


def build_model(model_dir, cfg=CFG):
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import paddle_tpu as fluid
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = SEED
    with fluid.program_guard(prog, startup):
        toks = fluid.layers.data(name='tokens',
                                 shape=[1, cfg.max_len, 1],
                                 dtype='int64', append_batch_size=False)
        from paddle_tpu.models.transformer import language_model_logits
        logits = language_model_logits(toks, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(model_dir, ['tokens'], [logits],
                                      exe, main_program=prog)


def solo_reference(model_dir):
    """The decoder a fleet's streams are compared with: each stream
    alone, cold, on a pool of its own that fits it."""
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    return AnalysisPredictor(AnalysisConfig(model_dir)).prepare_decoding(
        slots=1, page_tokens=4, kv_pages=8)


def make_prompts(seed, n, budget):
    """The workload: n (prompt, session) pairs, prompt + budget inside
    CFG.max_len. Deterministic in seed — the driver, a restarted
    driver, and the in-process reference all derive the same list."""
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        plen = int(rng.randint(2, 5))
        prompt = [int(t) for t in rng.randint(1, CFG.vocab, plen)]
        out.append((prompt, i % SESSIONS))
    return out


def make_disagg_prompts(seed, n, budget):
    """The disagg workload: every EVEN stream is a long prompt built
    from one shared 8-token system prefix (exactly two full 4-token
    pages — the chain the prefill tier ships) plus a 2-4 token seeded
    suffix; odd streams are short 2-3 token prompts whose chain has no
    full page at all, so their dispatch must short-circuit the wire.
    Returns (prompt, per-stream budget) pairs, budgets clipped so
    prompt + budget always fits CFG.max_len."""
    rng = np.random.RandomState(seed)
    shared = [int(t) for t in rng.randint(1, CFG.vocab, 8)]
    out = []
    for i in range(n):
        if i % 2 == 0:
            extra = int(rng.randint(2, 5))
            prompt = shared + [int(t)
                               for t in rng.randint(1, CFG.vocab, extra)]
        else:
            plen = int(rng.randint(2, 4))
            prompt = [int(t) for t in rng.randint(1, CFG.vocab, plen)]
        out.append((prompt, min(budget, CFG.max_len - len(prompt))))
    return out


def complete_replica(endpoint, timeout=30.0):
    """COMPLETE one replica (clean exit 0), retrying through a restart
    window — the killed replica may be mid-respawn."""
    from paddle_tpu.distributed import wire
    host, port = endpoint.rsplit(':', 1)
    deadline = time.monotonic() + timeout
    while True:
        try:
            with socket.create_connection((host, int(port)),
                                          timeout=2.0) as s:
                wire.write_msg(s, wire.COMPLETE, {'seq': 0})
                wire.read_msg(s)
            return True
        except (ConnectionError, OSError):
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.2)


def run_driver():
    from paddle_tpu.serving import FleetRouter
    replicas = os.environ['FLEET_REPLICAS'].split(',')
    seed = int(os.environ.get('FLEET_SEED', '0'))
    n = int(os.environ.get('FLEET_STREAMS', '24'))
    budget = int(os.environ.get('FLEET_BUDGET', '10'))
    router = FleetRouter(replicas, probe_secs=0.1)
    router.start()
    try:
        router.wait_healthy(timeout=120.0)
        reqs = [router.submit(p, max_new_tokens=budget, session=s)
                for p, s in make_prompts(seed, n, budget)]
        streams, states = [], []
        for r in reqs:
            r.wait(timeout=300.0)
            streams.append([int(t) for t in r.tokens])
            states.append(r.state)
        stats = router.stats()
    finally:
        router.stop()
    print('RESULT ' + json.dumps({
        'streams': streams, 'states': states,
        'failovers': stats['failovers'],
        'completed': stats['completed']}), flush=True)
    if os.environ.get('FLEET_COMPLETE', '1') == '1':
        for ep in replicas:
            complete_replica(ep)


def run_overload_driver():
    # the bit-exact reference below runs jax in THIS process — pin it
    # to CPU before anything touches a backend
    import jax
    jax.config.update('jax_platforms', 'cpu')
    from paddle_tpu.serving import FleetRouter, OverloadError
    replicas = os.environ['FLEET_REPLICAS'].split(',')
    seed = int(os.environ.get('FLEET_SEED', '0'))
    n = int(os.environ.get('FLEET_STREAMS', '40'))
    budget = int(os.environ.get('FLEET_BUDGET', '8'))
    model_dir = os.environ['FLEET_MODEL_DIR']
    prompts = make_prompts(seed, n, budget)
    # mixed tiers: every 3rd stream is the paying tier (priority 1),
    # the rest are best-effort tier 0 — the only tier allowed to shed
    prios = [1 if i % 3 == 0 else 0 for i in range(n)]
    router = FleetRouter(replicas, probe_secs=0.1)
    router.start()
    sheds = {0: 0, 1: 0}
    reqs = []
    try:
        router.wait_healthy(timeout=120.0)
        for (p, s), prio in zip(prompts, prios):
            try:
                reqs.append(router.submit(p, max_new_tokens=budget,
                                          session=s, priority=prio))
            except OverloadError:
                sheds[prio] += 1
                reqs.append(None)
        streams, states = [], []
        for r in reqs:
            if r is None:
                streams.append([])
                states.append('SHED')
                continue
            r.wait(timeout=600.0)
            streams.append([int(t) for t in r.tokens])
            states.append(r.state)
        stats = router.stats()
    finally:
        router.stop()
    # every stream that completed must be bit-exact against a solo
    # dense-decode reference over the same saved bytes — preemption,
    # swap/re-prefill resume and failover may reorder work, never
    # change tokens
    ref = solo_reference(model_dir)
    mismatches = 0
    for (p, _), st, toks in zip(prompts, states, streams):
        if st == 'DONE' and toks != [int(t) for t in
                                     ref.generate(p, budget)]:
            mismatches += 1
    print('RESULT ' + json.dumps({
        'submitted': n,
        'done': sum(1 for s in states if s == 'DONE'),
        'high_sheds': sheds[1],
        'high_bad': sum(1 for s, pr in zip(states, prios)
                        if pr > 0 and s != 'DONE'),
        'low_sheds': sheds[0],
        'low_failed': sum(1 for s, pr in zip(states, prios)
                          if pr <= 0 and s == 'FAILED'),
        'mismatches': mismatches,
        'failovers': stats['failovers'],
        'preemptions': stats['preemptions'],
        'cache_sheds': stats['cache_sheds']}), flush=True)
    if os.environ.get('FLEET_COMPLETE', '1') == '1':
        for ep in replicas:
            complete_replica(ep)


def _warm_replica(endpoint, prompt, budget, timeout=180.0):
    """Heat one replica's compile caches with a throwaway stream over a
    direct wire connection. Completion is watched via SRV_HEALTH (the
    active/queue counters), NOT SRV_POLL: a seeded grayfail stall
    triggers on the Nth SRV_POLL, and warmup must not consume that
    count — nor may cold-compile first-token latency ever be visible
    to the progress watchdog, which is why warmup happens before the
    driver arms it."""
    from paddle_tpu.distributed import wire
    host, port = endpoint.rsplit(':', 1)
    deadline = time.monotonic() + timeout
    while True:       # the replica binds only after its model loads
        try:
            s = socket.create_connection((host, int(port)), timeout=5.0)
            break
        except OSError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.25)
    with s:
        s.settimeout(timeout)
        wire.write_msg(s, wire.SRV_SUBMIT,
                       {'seq': 0, 'rid': 'warm', 'mnt': int(budget)},
                       np.asarray(prompt, np.int64))
        wire.read_msg(s)
        seq = 1
        while True:
            wire.write_msg(s, wire.SRV_HEALTH, {'seq': seq})
            _, meta, _ = wire.read_msg(s)
            if not meta.get('active') and not meta.get('queue_depth'):
                return
            if time.monotonic() >= deadline:
                raise RuntimeError('warmup of %s timed out' % endpoint)
            seq += 1
            time.sleep(0.25)


def run_grayfail_driver():
    # the bit-exact reference runs jax in THIS process — pin CPU first
    import jax
    jax.config.update('jax_platforms', 'cpu')
    replicas = os.environ['FLEET_REPLICAS'].split(',')
    seed = int(os.environ.get('FLEET_SEED', '0'))
    n = int(os.environ.get('FLEET_STREAMS', '12'))
    budget = int(os.environ.get('FLEET_BUDGET', '10'))
    model_dir = os.environ['FLEET_MODEL_DIR']
    prompts = make_prompts(seed, n, budget)
    # every 3rd stream is the paying tier, carrying an end-to-end
    # deadline generous enough that only a LOST stream (not a slow
    # one) could breach it — the acceptance is zero tier-1 violations
    # even while replica 0 stalls mid-stream
    prios = [1 if i % 3 == 0 else 0 for i in range(n)]
    for ep in replicas:
        _warm_replica(ep, prompts[0][0], budget)
    # arm the gray-failure machinery only now, with all replicas warm
    # (the router reads these flags at construction; env was already
    # bootstrapped at import, so go through set_flags)
    from paddle_tpu import flags
    flags.set_flags({'FLAGS_fleet_progress_timeout_secs':
                     os.environ.get('GRAYFAIL_PROGRESS_TIMEOUT', '2.0')})
    from paddle_tpu.serving import FleetRouter
    # fast polling so the seeded stall's Nth-SRV_POLL trigger lands
    # well inside the burst window on any machine speed
    router = FleetRouter(replicas, poll_secs=0.005, probe_secs=0.1)
    router.start()
    try:
        router.wait_healthy(timeout=120.0)
        reqs = [router.submit(p, max_new_tokens=budget, session=s,
                              priority=prio,
                              deadline_ms=120000.0 if prio > 0 else None)
                for (p, s), prio in zip(prompts, prios)]
        streams, states = [], []
        for r in reqs:
            r.wait(timeout=300.0)
            streams.append([int(t) for t in r.tokens])
            states.append(r.state)
        stats = router.stats()
    finally:
        router.stop()
    # the in-harness bit-exactness gate: a stream that survived a
    # gray-mark failover (or a deadline near-miss) must be
    # np.array_equal to the solo decode reference — gray
    # tolerance may move work, never change tokens
    ref = solo_reference(model_dir)
    mismatches = 0
    for (p, _), st, toks in zip(prompts, states, streams):
        want = np.asarray([int(t) for t in ref.generate(p, budget)],
                          np.int64)
        if st != 'DONE' or not np.array_equal(
                np.asarray(toks, np.int64), want):
            mismatches += 1
    print('RESULT ' + json.dumps({
        'submitted': n,
        'done': sum(1 for s in states if s == 'DONE'),
        'states': states,
        'streams': streams,
        'mismatches': mismatches,
        'high_bad': sum(1 for s, pr in zip(states, prios)
                        if pr > 0 and s != 'DONE'),
        'gray_marks': stats['gray_marks'],
        'hedges': stats['hedges'],
        'hedge_wins': stats['hedge_wins'],
        'deadline_expired': stats['deadline_expired'],
        'failovers': stats['failovers']}), flush=True)
    if os.environ.get('FLEET_COMPLETE', '1') == '1':
        for ep in replicas:
            complete_replica(ep)


def run_disagg_driver():
    # the bit-exact reference runs jax in THIS process — pin CPU first
    import jax
    jax.config.update('jax_platforms', 'cpu')
    replicas = os.environ['FLEET_REPLICAS'].split(',')
    prefill_eps = [e for e in
                   os.environ.get('FLEET_PREFILL', '').split(',') if e]
    seed = int(os.environ.get('FLEET_SEED', '0'))
    n = int(os.environ.get('FLEET_STREAMS', '16'))
    budget = int(os.environ.get('FLEET_BUDGET', '4'))
    model_dir = os.environ['FLEET_MODEL_DIR']
    work = make_disagg_prompts(seed, n, budget)
    # warm EVERY tier over direct wire connections first: the prefill
    # replica's cold jit compile must never race the decode tier's
    # FLAGS_disagg_ship_timeout, and warmup must not consume the
    # seeded fault rule (it is keyed to SRV_PAGE_FETCH, which warmup
    # never sends)
    for ep in replicas + prefill_eps:
        _warm_replica(ep, [1, 2, 3], 2)
    from paddle_tpu.serving import FleetRouter
    router = FleetRouter(replicas, prefill_replicas=prefill_eps,
                         poll_secs=0.005, probe_secs=0.1)
    router.start()
    try:
        router.wait_healthy(timeout=120.0)
        reqs = [router.submit(p, max_new_tokens=b) for p, b in work]
        streams, states = [], []
        for r in reqs:
            r.wait(timeout=300.0)
            streams.append([int(t) for t in r.tokens])
            states.append(r.state)
        # one probe period so the replicas' ship/reprefill counters
        # (SRV_HEALTH truth) land in the router's aggregates
        time.sleep(0.6)
        stats = router.stats()
    finally:
        router.stop()
    ref = solo_reference(model_dir)
    mismatches = 0
    for (p, b), st, toks in zip(work, states, streams):
        want = np.asarray([int(t) for t in ref.generate(p, b)],
                          np.int64)
        if st != 'DONE' or not np.array_equal(
                np.asarray(toks, np.int64), want):
            mismatches += 1
    print('RESULT ' + json.dumps({
        'submitted': n,
        'done': sum(1 for s in states if s == 'DONE'),
        'states': states,
        'streams': streams,
        'mismatches': mismatches,
        'failovers': stats['failovers'],
        'local_reprefills': stats['local_reprefills'],
        'pages_shipped': stats['pages_shipped'],
        'ship_bytes': stats['ship_bytes'],
        'prefix_hit_rate': stats['prefix_hit_rate'],
        'prefix_dir_entries': stats['prefix_dir_entries']}),
        flush=True)
    if os.environ.get('FLEET_COMPLETE', '1') == '1':
        for ep in replicas + prefill_eps:
            complete_replica(ep)


def main():
    role = os.environ['FLEET_ROLE']
    if role == 'build':
        build_model(os.environ['FLEET_MODEL_DIR'])
    elif role == 'driver':
        run_driver()
    elif role == 'overload':
        run_overload_driver()
    elif role == 'grayfail':
        run_grayfail_driver()
    elif role == 'disagg':
        run_disagg_driver()
    else:
        raise SystemExit('unknown FLEET_ROLE %r' % role)


if __name__ == '__main__':
    main()
