"""Chaos sweep: run the dist-training smoke under many seeded
FaultPlans and classify each outcome.

For every seed in the range, `FaultPlan.from_seed(seed)` generates a
deterministic plan (1-3 rules over SEND_VAR / BATCH_BARRIER / GET_VAR /
FETCH_BARRIER with drop / close / delay / error actions), trainer 0 of
a 2x2 sync cluster runs under it via FLAGS_fault_plan, and the final
weights are compared against the local single-process baseline. A seed
is:

  ok        faulted cluster matched the baseline weights (replay +
            dedup held)
  diverged  cluster finished but weights differ (a replay was lost or
            double-applied -- a REAL bug, report the seed)
  fatal     a worker exited non-zero (a plan with a non-retryable
            error rule, or retries exhausted -- expected for the ~5%
            of rules that are fatal errors)
  hung      the cluster blew the per-seed time budget and was killed

`--kill` switches to the elastic-recovery sweep: each seed picks a
victim role (trainer 0 or pserver 0) and a kill point
(`FaultPlan.from_kill_seed` -- one `exit` rule, the deterministic
kill -9 analog), and the cluster runs under `distributed.Supervisor`
with pserver snapshots enabled, so the victim is RESTARTED: a trainer
rejoins under a bumped incarnation, a pserver resumes from its
snapshot + journal. Verdicts:

  recovered  the victim died, was restarted, and final weights match
             the fault-free baseline bit-exactly
  diverged   cluster finished after the kill but weights differ (a
             recovery bug -- report the seed)
  fatal      a role exhausted its restart budget
  hung       the supervised cluster blew the time budget

`--mesh-kill` is the sharded-mesh flavor of `--kill`: one
Supervisor-run mesh trainer (tests/mesh_worker.py — 8 virtual CPU
devices, ZeRO-3 parameter sharding, CheckpointConfig(sharded=True)
generations under paddle_tpu/checkpoint/) is kill-9'd at a seeded step
and restarted; the resumed run must match a fault-free mesh baseline
**bit-exactly** (np.array_equal, not allclose — the checkpoint path
replays the identical arithmetic). Same verdicts as --kill.

`--corrupt` switches the generator to `FaultPlan.from_corrupt_seed`:
plans of bit-flip (`corrupt`) and poisoned-gradient (`nan`) rules on
trainer 0's sends. Unlike the drop/close/error sweep, every corrupt
plan should end `ok` — the wire CRC rejects flipped frames retryably
and the pserver finite guard rejects NaN payloads retryably, so the
retry resends the clean value in both cases; `fatal`/`hung` here means
an integrity hole, not a plan-dependent outcome.

`--refresh` chaoses the online-learning loop (paddle_tpu/online/): a
1-trainer cluster trains through its sync rounds while a SEPARATE
serving process tracks the pserver fleet's published param versions
via ParamSubscriber (tests/online_worker.py roles). Each seed faults
pserver 0 — bit-flipped outbound replies, or a kill mid-traffic under
the restarting Supervisor — and the serving process (never restarted)
must end installed at version == steps with param digests matching the
trainer's final pull: corrupt pulls keep the old version serving until
a clean retry, a shard outage just stalls staleness. Verdicts: `ok`
(corrupt plan survived), `recovered`/`nokill` (kill plan, shard
restarted / kill point never fired), `diverged` (serving's installed
bytes differ from the trainer's — a refresh-integrity bug, report the
seed), plus the usual `fatal`/`hung`.

`--fleet` chaoses the fleet serving topology (paddle_tpu/serving/
fleet.py): two serve_replica.py processes plus one FleetRouter driver
(tests/fleet_worker.py) run a fixed seeded workload of greedy streams,
and each seed kill-9's EITHER replica 0 (a seeded `exit` on its recv
side) or the router driver itself (seeded `exit` on its send side) —
both speak the wire, so the kill lands at a deterministic message.
The restarting Supervisor brings the victim back; acceptance is that
the driver's final RESULT (a restarted driver re-runs the whole
workload from the same seed) matches the fault-free fleet baseline
BIT-exactly: greedy failover re-prefill must change no stream.
Verdicts: `recovered`/`nokill` (kill fired / kill point never
reached), `diverged` (a stream changed — a failover-determinism bug,
report the seed), plus the usual `fatal`/`hung`.

`--overload` chaoses the preempt-first capacity path (serving/
preempt.py + the engine tier queues): each seed fires a 10x
mixed-tier burst (every 3rd stream priority 1) from an overload
driver (tests/fleet_worker.py) at two PAGED replicas sized far below
the burst (2 slots over 6 four-token pages), and kill-9's replica 0
at a seeded wire message under the restarting Supervisor. The
replicas must preempt tier-0 streams (host-RAM page swap, or drop +
re-prefill when the budget is dry) to make room. Verdicts:
`recovered`/`nokill` as usual, `diverged` when the SLO contract
breaks — ANY high-tier shed or failure, any low-tier FAILED stream,
or any completed stream whose tokens differ from the solo reference —
and `fatal` additionally when serving.preemptions stayed 0 (the seed
never exercised the machinery it gates).

`--grayfail` chaoses the fail-SLOW half of the failure model: replica
0 runs under `FaultPlan.from_grayfail_seed` (one seeded ``stall`` rule
— at the Nth inbound SRV_POLL its data connection freezes for 20-40s
while SRV_HEALTH keeps answering on other connections), and the
grayfail driver (tests/fleet_worker.py) runs a warmed mixed-tier
workload with the router's progress watchdog armed. Acceptance:
every stream completes bit-exact (np.array_equal, in-driver) against
the solo reference, the watchdog gray-marked the stalled replica
(fleet.gray_marks >= 1 — `fatal` when the stall fired unseen), and
zero high-tier deadline violations. Verdicts: `recovered` (stall
fired, caught, streams intact), `nokill` (the Nth poll was never
reached), `diverged` (a stream changed or a tier-1 SLO broke), plus
the usual `fatal`/`hung`.

`--disagg` chaoses the disaggregated prefill/decode path (serving/
disagg.py + the fleet prefix directory): two PAGED decode replicas,
one PAGED prefill-tier replica, and the disagg driver
(tests/fleet_worker.py) run a seeded mixed burst where every other
stream shares one 8-token system prefix (two full shippable pages),
so long streams dispatch with meta['prefill_from'] and the decode
tier pulls pages over SRV_PAGE_FETCH. Each seed either kill-9's the
prefill replica at a seeded SRV_PAGE_FETCH (the restarting
Supervisor brings it back) or gray-stalls that fetch connection for
20-40s while FLAGS_disagg_ship_timeout=2s forces the ship to give
up. Acceptance: every stream DONE and bit-exact (in-driver
np.array_equal against the solo reference), and once the fault
demonstrably fired, fleet.failovers + local re-prefills >= 1 — a
dead or frozen prefill tier may cost latency, never tokens.
Verdicts: `recovered` (fault fired, ship fell back, streams intact),
`nokill` (the Nth fetch was never reached), `diverged` (a stream
changed or failed), `fatal` additionally when the fault fired but no
fallback engaged, plus the usual `hung`.

`--mesh-serve` is the GSPMD flavor of `--fleet`: the same two-replica
topology, but every replica serves mesh-sharded (SERVE_MESH_SHAPE=tp=2
over 8 virtual CPU devices — the XLA_FLAGS device-count override rides
the role env so it lands before the child imports jax), while the
fault-free baseline run stays SINGLE-chip. Each seed kill-9's the
mesh-backed replica 0 at a seeded wire message under the restarting
Supervisor, so acceptance gates two properties at once: failover off a
dead sharded replica, and the recovered streams matching the
single-chip baseline BIT-exactly (GSPMD decode must change no token).
Same verdicts as `--fleet`.

`--quick` is the CI smoke shape: 3 seeds by default, and the exit
status is ALSO non-zero on any fatal/hung seed (a quick sweep exists
to gate regressions, so every non-ok outcome fails it).

Usage:
    python tools/chaos_sweep.py                     # seeds 0..19
    python tools/chaos_sweep.py --seeds 100 --steps 4
    python tools/chaos_sweep.py --seed-start 7 --seeds 1 --verbose
    python tools/chaos_sweep.py --kill --seeds 10   # process-kill mode
    python tools/chaos_sweep.py --corrupt --quick   # integrity smoke
    python tools/chaos_sweep.py --mesh-kill --quick # sharded-mesh kill
    python tools/chaos_sweep.py --refresh --quick   # online-refresh chaos
    python tools/chaos_sweep.py --fleet --quick     # fleet replica/router kill
    python tools/chaos_sweep.py --overload --quick  # preempt-first capacity
    python tools/chaos_sweep.py --grayfail --quick  # gray-failure watchdog
    python tools/chaos_sweep.py --disagg --quick    # prefill-tier kill/stall
    python tools/chaos_sweep.py --mesh-serve --quick # mesh-replica kill

Exit status is non-zero iff any seed DIVERGED (or, under --quick, any
seed was fatal/hung): fatal/hung seeds of the full sweep are
plan-dependent outcomes, weight divergence is never acceptable.
"""
from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, _ROOT)
sys.path.insert(0, os.path.join(_ROOT, 'tests'))

_WORKER = os.path.join(_ROOT, 'tests', 'ps_worker.py')
_MESH_WORKER = os.path.join(_ROOT, 'tests', 'mesh_worker.py')
_ONLINE_WORKER = os.path.join(_ROOT, 'tests', 'online_worker.py')
_FLEET_WORKER = os.path.join(_ROOT, 'tests', 'fleet_worker.py')
_SERVE_REPLICA = os.path.join(_ROOT, 'tools', 'serve_replica.py')


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


def _obs_env(env, obs_dir, role_name):
    """Plant the per-role observability env (same layout Supervisor
    uses: one subdir per role, role name = timeline lane)."""
    if obs_dir:
        role_obs = os.path.join(obs_dir, role_name)
        os.makedirs(role_obs, exist_ok=True)
        env['FLAGS_obs_dir'] = role_obs
        env['FLAGS_obs_role'] = role_name
        env['FLAGS_obs_flush_secs'] = '0.5'
    return env


def _run_seed(plan_json, model, steps, trainers, pservers, budget,
              obs_dir=None):
    eps = ','.join('127.0.0.1:%d' % p for p in _free_ports(pservers))
    base_env = dict(os.environ)
    base_env['JAX_PLATFORMS'] = 'cpu'
    base_env.pop('XLA_FLAGS', None)
    base_env.update({'PS_MODEL': model, 'PS_ENDPOINTS': eps,
                     'PS_TRAINERS': str(trainers), 'PS_STEPS': str(steps),
                     'PS_SYNC': '1', 'PS_OPTIMIZER': 'sgd'})
    pprocs = []
    for i in range(pservers):
        env = dict(base_env, PS_ROLE='pserver', PS_PSERVER_ID=str(i))
        _obs_env(env, obs_dir, 'pserver%d' % i)
        pprocs.append(subprocess.Popen(
            [sys.executable, _WORKER], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    tprocs = []
    for i in range(trainers):
        env = dict(base_env, PS_ROLE='trainer', PS_TRAINER_ID=str(i))
        _obs_env(env, obs_dir, 'trainer%d' % i)
        if i == 0:
            env['FLAGS_fault_plan'] = plan_json
        tprocs.append(subprocess.Popen(
            [sys.executable, _WORKER], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    deadline = time.monotonic() + budget
    outs, hung = [], False
    # drain TRAINERS first: a trainer's RESULT line (full weights) can
    # exceed the 64 KB pipe buffer, and it is written before the
    # trainer's COMPLETE teardown -- waiting on a pserver while trainer
    # pipes are full deadlocks the whole cluster
    for p in tprocs + pprocs:
        left = deadline - time.monotonic()
        try:
            out, _ = p.communicate(timeout=max(left, 0.1))
        except subprocess.TimeoutExpired:
            hung = True
            p.kill()
            out, _ = p.communicate()
        outs.append(out)
    if hung:
        for p in tprocs + pprocs:     # reap anything still up
            if p.poll() is None:
                p.kill()
                p.communicate()
        return 'hung', None, outs
    if any(p.returncode != 0 for p in tprocs + pprocs):
        return 'fatal', None, outs
    weights = None
    for ln in outs[0].splitlines():   # trainer 0's RESULT
        if ln.startswith('RESULT '):
            weights = json.loads(ln[len('RESULT '):])['weights']
    return ('ok', weights, outs) if weights else ('fatal', None, outs)


def _run_kill_seed(seed, model, steps, trainers, pservers, budget,
                   workdir, obs_dir=None):
    """One --kill seed under the Supervisor: returns (verdict, weights,
    victim, plan_json, outs)."""
    import random

    from paddle_tpu.distributed.resilience import FaultPlan
    from paddle_tpu.distributed.supervisor import Supervisor

    role = random.Random(('victim', seed).__repr__()).choice(
        ['trainer', 'pserver'])
    plan = FaultPlan.from_kill_seed(seed, role)
    eps = ','.join('127.0.0.1:%d' % p for p in _free_ports(pservers))
    base_env = dict(os.environ)
    base_env['JAX_PLATFORMS'] = 'cpu'
    base_env.pop('XLA_FLAGS', None)
    base_env.update({'PS_MODEL': model, 'PS_ENDPOINTS': eps,
                     'PS_TRAINERS': str(trainers), 'PS_STEPS': str(steps),
                     'PS_SYNC': '1', 'PS_OPTIMIZER': 'sgd',
                     # cover the victim's death + supervisor backoff +
                     # restart without retiring anyone as silently dead
                     'FLAGS_rpc_deadline': '120',
                     'FLAGS_rpc_max_retries': '12',
                     'FLAGS_rpc_reconnect_secs': '10'})
    if obs_dir:
        base_env['FLAGS_obs_flush_secs'] = '0.5'
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    for i in range(pservers):
        env = dict(base_env, PS_ROLE='pserver', PS_PSERVER_ID=str(i),
                   FLAGS_ps_state_path=os.path.join(
                       workdir, 'ps%d_s%d.state' % (i, seed)))
        if role == 'pserver' and i == 0:
            env['FLAGS_fault_plan'] = plan.to_json()
        sup.add_role('pserver%d' % i,
                     [sys.executable, _WORKER], env=env)
    for i in range(trainers):
        env = dict(base_env, PS_ROLE='trainer', PS_TRAINER_ID=str(i))
        if role == 'trainer' and i == 0:
            env['FLAGS_fault_plan'] = plan.to_json()
        sup.add_role('trainer%d' % i,
                     [sys.executable, _WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=budget)
    outs = [sup.output(n) for n in sorted(states)]
    victim = '%s0' % role
    try:
        if any(s in ('running', 'backoff') for s in states.values()):
            return 'hung', None, victim, plan.to_json(), outs
        if any(s == 'failed' for s in states.values()):
            return 'fatal', None, victim, plan.to_json(), outs
        weights = None
        for ln in sup.output('trainer0').splitlines():
            if ln.startswith('RESULT '):
                weights = json.loads(ln[len('RESULT '):])['weights']
        if weights is None:
            return 'fatal', None, victim, plan.to_json(), outs
        if sup.restarts[victim] == 0:
            # the kill point never fired (nth beyond the run's message
            # count) -- a clean run, counted ok but labeled
            return 'nokill', weights, victim, plan.to_json(), outs
        return 'recovered', weights, victim, plan.to_json(), outs
    finally:
        sup.stop()


def _run_mesh_seed(kill_nth, steps, budget, workdir, obs_dir=None,
                   dp=4, tp=1):
    """One supervised mesh-trainer run; kill_nth=None is the fault-free
    baseline. Returns (verdict, weights, plan_json, outs) — verdict
    'ok' means the run finished; recovered/nokill are decided by the
    caller from the restart count."""
    from paddle_tpu.distributed.supervisor import Supervisor

    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env.pop('XLA_FLAGS', None)
    env.update({'MESH_STEPS': str(steps), 'MESH_CKPT':
                os.path.join(workdir, 'ckpt'), 'MESH_CKPT_EVERY': '2',
                'MESH_DP': str(dp), 'MESH_TP': str(tp)})
    plan_json = ''
    if kill_nth is not None:
        plan_json = json.dumps({'rules': [{
            'when': 'step', 'type': '*', 'nth': int(kill_nth),
            'action': 'exit'}]})
        env['FLAGS_fault_plan'] = plan_json
    if obs_dir:
        env['FLAGS_obs_flush_secs'] = '0.5'
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    sup.add_role('mesh', [sys.executable, _MESH_WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=budget)
    out = sup.output('mesh')
    restarts = sup.restarts['mesh']
    sup.stop()
    if any(s in ('running', 'backoff') for s in states.values()):
        return 'hung', None, plan_json, [out]
    if any(s == 'failed' for s in states.values()):
        return 'fatal', None, plan_json, [out]
    weights = None
    for ln in out.splitlines():
        if ln.startswith('RESULT '):
            weights = json.loads(ln[len('RESULT '):])['weights']
    if weights is None:
        return 'fatal', None, plan_json, [out]
    if kill_nth is None:
        return 'ok', weights, plan_json, [out]
    return (('recovered' if restarts else 'nokill'),
            weights, plan_json, [out])


def _run_refresh_seed(seed, steps, pservers, budget, workdir,
                      obs_dir=None):
    """One --refresh seed: trainer x pservers x ONE serving process
    (tests/online_worker.py roles) under the Supervisor, with a seeded
    fault on pserver 0 — either bit-flipped outbound replies (the
    subscriber's pull path must reject the corrupt frame and keep the
    old version serving until a clean retry) or a kill mid-traffic (the
    Supervisor restarts the shard from its snapshot and the refresh
    loop rides out the outage). The serving process is NEVER
    restarted; acceptance is that it ends installed at version ==
    steps with param digests matching the trainer's final pull.
    Returns (verdict, fault_mode, plan_json, outs)."""
    import random

    from paddle_tpu.distributed.supervisor import Supervisor

    rng = random.Random(('refresh', seed).__repr__())
    mode = rng.choice(['corrupt', 'kill'])
    if mode == 'corrupt':
        rules = [{'when': 'send', 'type': 'REPLY_VAR',
                  'nth': rng.randint(1, 6), 'action': 'corrupt',
                  'bits': rng.randint(1, 8)}
                 for _ in range(rng.randint(1, 2))]
    else:
        rules = [{'when': 'recv',
                  'type': rng.choice(['GET_VERSION', 'GET_VARS',
                                      'SEND_VAR']),
                  'nth': rng.randint(2, 8), 'action': 'exit'}]
    plan_json = json.dumps({'rules': rules})

    eps = ','.join('127.0.0.1:%d' % p for p in _free_ports(pservers))
    base_env = dict(os.environ)
    base_env.pop('XLA_FLAGS', None)
    base_env.update({'PS_ENDPOINTS': eps, 'PS_STEPS': str(steps),
                     'ON_DIR': workdir,
                     'FLAGS_online_poll_secs': '0.1',
                     'FLAGS_rpc_deadline': '120',
                     'FLAGS_rpc_max_retries': '12',
                     'FLAGS_rpc_reconnect_secs': '10'})
    if obs_dir:
        base_env['FLAGS_obs_flush_secs'] = '0.5'
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    for i in range(pservers):
        env = dict(base_env, ON_ROLE='pserver', PS_PSERVER_ID=str(i),
                   FLAGS_ps_state_path=os.path.join(
                       workdir, 'ps%d_s%d.state' % (i, seed)))
        if i == 0:
            env['FLAGS_fault_plan'] = plan_json
        sup.add_role('pserver%d' % i,
                     [sys.executable, _ONLINE_WORKER], env=env)
    sup.add_role('trainer0', [sys.executable, _ONLINE_WORKER],
                 env=dict(base_env, ON_ROLE='trainer'))
    # serving must survive the whole seed on its own refresh machinery:
    # a serving crash (or restart) is a finding, not a recovery
    sup.add_role('serving0', [sys.executable, _ONLINE_WORKER],
                 env=dict(base_env, ON_ROLE='serving'),
                 restartable=False)
    sup.start()
    states = sup.wait(timeout=budget)
    outs = [sup.output(n) for n in sorted(states)]
    try:
        if any(s in ('running', 'backoff') for s in states.values()):
            return 'hung', mode, plan_json, outs
        if any(s == 'failed' for s in states.values()):
            return 'fatal', mode, plan_json, outs

        def result_of(name):
            for ln in sup.output(name).splitlines():
                if ln.startswith('RESULT '):
                    return json.loads(ln[len('RESULT '):])
            return None
        trainer, serving = result_of('trainer0'), result_of('serving0')
        if trainer is None or serving is None:
            return 'fatal', mode, plan_json, outs
        if serving['installed_version'] != steps:
            return 'diverged', mode, plan_json, outs
        for name, digest in serving['digests'].items():
            # the bytes serving installed must be the bytes the
            # trainer's final fetch_barrier pulled — end-to-end, per
            # param, regardless of what the fault did in between
            if trainer['digests'].get(name) != digest:
                return 'diverged', mode, plan_json, outs
        if mode == 'kill':
            return (('recovered' if sup.restarts['pserver0'] else
                     'nokill'), mode, plan_json, outs)
        return 'ok', mode, plan_json, outs
    finally:
        sup.stop()


def _run_fleet_seed(seed, budget, workdir, model_dir, baseline,
                    n_replicas=2, streams=24, gen=10, obs_dir=None,
                    mesh=''):
    """One --fleet seed: n serve_replica.py processes + a FleetRouter
    driver (tests/fleet_worker.py) under the Supervisor, with a seeded
    exit fault on either replica 0 (recv side) or the driver (send
    side). baseline=None is the fault-free reference run (returns its
    streams); otherwise the driver's LAST RESULT line — a restarted
    driver re-runs the identical seeded workload from scratch — must
    match the baseline streams bit-exactly. The workload seed is FIXED
    (only the kill point varies per sweep seed) so every run is
    comparable. mesh='tp=2' (the --mesh-serve sweep) serves every
    replica GSPMD-sharded over 8 virtual CPU devices; the victim is
    then always the mesh-backed replica 0, and the single-chip
    baseline makes bit-exactness a cross-sharding check too.
    Returns (verdict, streams, victim, plan_json, outs)."""
    import random

    from paddle_tpu.distributed.supervisor import Supervisor

    ports = _free_ports(n_replicas)
    eps = ['127.0.0.1:%d' % p for p in ports]
    rng = random.Random((('mesh-serve' if mesh else 'fleet'),
                         seed).__repr__())
    victim, plan_json = None, ''
    if baseline is not None:
        victim = ('replica0' if mesh else
                  rng.choice(['replica0', 'driver']))
        plan_json = json.dumps({'rules': [{
            'when': 'recv' if victim == 'replica0' else 'send',
            'type': '*', 'nth': rng.randint(15, 90),
            'action': 'exit'}]})
    base_env = dict(os.environ)
    base_env['JAX_PLATFORMS'] = 'cpu'
    base_env.pop('XLA_FLAGS', None)
    if obs_dir:
        base_env['FLAGS_obs_flush_secs'] = '0.5'
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    for i, ep in enumerate(eps):
        # fixed ports (not ephemeral): a restarted replica rebinds the
        # SAME endpoint, so the router's reconnects find it again
        env = dict(base_env, SERVE_MODEL_DIR=model_dir,
                   SERVE_ENDPOINT=ep, SERVE_SLOTS='4',
                   SERVE_WORKERS='1')
        if mesh:
            # the device-count override must ride the role env — it
            # has to be in place before the replica process imports
            # jax (see serve_replica.py's SERVE_MESH_SHAPE contract)
            env['SERVE_MESH_SHAPE'] = mesh
            env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
        if victim == 'replica0' and i == 0:
            env['FLAGS_fault_plan'] = plan_json
        sup.add_role('replica%d' % i,
                     [sys.executable, _SERVE_REPLICA], env=env)
    env = dict(base_env, FLEET_ROLE='driver',
               FLEET_REPLICAS=','.join(eps), FLEET_SEED='0',
               FLEET_STREAMS=str(streams), FLEET_BUDGET=str(gen))
    if victim == 'driver':
        env['FLAGS_fault_plan'] = plan_json
    sup.add_role('driver', [sys.executable, _FLEET_WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=budget)
    outs = [sup.output(n) for n in sorted(states)]
    try:
        if any(s in ('running', 'backoff') for s in states.values()):
            return 'hung', None, victim, plan_json, outs
        if any(s == 'failed' for s in states.values()):
            return 'fatal', None, victim, plan_json, outs
        result = None
        for ln in sup.output('driver').splitlines():
            if ln.startswith('RESULT '):
                result = json.loads(ln[len('RESULT '):])
        if result is None or any(s != 'DONE' for s in result['states']):
            return 'fatal', None, victim, plan_json, outs
        if baseline is None:
            return 'ok', result['streams'], victim, plan_json, outs
        if result['streams'] != baseline:
            return 'diverged', result['streams'], victim, plan_json, outs
        return (('recovered' if sup.restarts[victim] else 'nokill'),
                result['streams'], victim, plan_json, outs)
    finally:
        sup.stop()


def _run_overload_seed(seed, budget, workdir, model_dir, n_replicas=2,
                       streams=40, gen=8, obs_dir=None):
    """One --overload seed: a seeded 10x mixed-tier burst (every 3rd
    stream priority 1) against paged replicas sized far below the
    burst (2 slots, 6 pages of 4 tokens each), plus a seeded kill-9 of
    replica 0 under the restarting Supervisor. The replicas MUST
    preempt tier-0 streams (swap or re-prefill) to finish; acceptance
    is the preempt-first SLO contract — ZERO high-tier sheds or
    failures, every completed stream bit-exact against the solo
    reference (the driver self-checks), and serving.preemptions >= 1
    so the machinery demonstrably fired. Returns (verdict, result,
    victim, plan_json, outs)."""
    import random

    from paddle_tpu.distributed.supervisor import Supervisor

    ports = _free_ports(n_replicas)
    eps = ['127.0.0.1:%d' % p for p in ports]
    rng = random.Random(('overload', seed).__repr__())
    victim = 'replica0'
    plan_json = json.dumps({'rules': [{
        'when': 'recv', 'type': '*', 'nth': rng.randint(15, 90),
        'action': 'exit'}]})
    base_env = dict(os.environ)
    base_env['JAX_PLATFORMS'] = 'cpu'
    base_env.pop('XLA_FLAGS', None)
    if obs_dir:
        base_env['FLAGS_obs_flush_secs'] = '0.5'
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    for i, ep in enumerate(eps):
        # tight paged pool: 2 slots over 6 x 4-token pages — two
        # concurrent full-budget streams cannot both fit, so decode
        # pressure forces preemption instead of merely queueing
        env = dict(base_env, SERVE_MODEL_DIR=model_dir,
                   SERVE_ENDPOINT=ep, SERVE_SLOTS='2',
                   SERVE_WORKERS='1',
                   SERVE_PAGE_TOKENS='4', SERVE_KV_PAGES='6',
                   SERVE_PREFILL_CHUNK='16')
        if i == 0:
            env['FLAGS_fault_plan'] = plan_json
        sup.add_role('replica%d' % i,
                     [sys.executable, _SERVE_REPLICA], env=env)
    env = dict(base_env, FLEET_ROLE='overload',
               FLEET_MODEL_DIR=model_dir,
               FLEET_REPLICAS=','.join(eps), FLEET_SEED='0',
               FLEET_STREAMS=str(streams), FLEET_BUDGET=str(gen))
    sup.add_role('driver', [sys.executable, _FLEET_WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=budget)
    outs = [sup.output(n) for n in sorted(states)]
    try:
        if any(s in ('running', 'backoff') for s in states.values()):
            return 'hung', None, victim, plan_json, outs
        if any(s == 'failed' for s in states.values()):
            return 'fatal', None, victim, plan_json, outs
        result = None
        for ln in sup.output('driver').splitlines():
            if ln.startswith('RESULT '):
                result = json.loads(ln[len('RESULT '):])
        if result is None:
            return 'fatal', None, victim, plan_json, outs
        if (result['high_sheds'] or result['high_bad'] or
                result['low_failed'] or result['mismatches']):
            # an SLO breach or a token divergence — the bug class this
            # sweep exists to catch
            return 'diverged', result, victim, plan_json, outs
        if result['preemptions'] < 1:
            # the burst never forced a preemption: the seed did not
            # exercise the machinery it gates on
            return 'fatal', result, victim, plan_json, outs
        return (('recovered' if sup.restarts[victim] else 'nokill'),
                result, victim, plan_json, outs)
    finally:
        sup.stop()


def _run_grayfail_seed(seed, budget, workdir, model_dir, n_replicas=2,
                       streams=24, gen=12, obs_dir=None):
    """One --grayfail seed: replica 0 is alive-but-stalled (a seeded
    ``stall`` rule freezes its data connection at the Nth SRV_POLL for
    20-40s while health probes keep passing) and the grayfail driver
    (tests/fleet_worker.py) runs a warmed mixed-tier workload with the
    progress watchdog armed. Nothing dies and nothing restarts — the
    whole point is that fail-slow looks NOTHING like fail-stop — so
    the verdict comes from the driver's RESULT counters: bit-exact
    streams (in-driver np.array_equal against the solo reference),
    fleet.gray_marks >= 1 once the stall demonstrably fired (the
    audit line in replica 0's log), zero high-tier violations.
    Returns (verdict, result, victim, plan_spec, outs)."""
    from paddle_tpu.distributed.supervisor import Supervisor

    ports = _free_ports(n_replicas)
    eps = ['127.0.0.1:%d' % p for p in ports]
    victim = 'replica0'
    plan_spec = 'grayfail:replica0:%d' % seed
    base_env = dict(os.environ)
    base_env['JAX_PLATFORMS'] = 'cpu'
    base_env.pop('XLA_FLAGS', None)
    if obs_dir:
        base_env['FLAGS_obs_flush_secs'] = '0.5'
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    for i, ep in enumerate(eps):
        env = dict(base_env, SERVE_MODEL_DIR=model_dir,
                   SERVE_ENDPOINT=ep, SERVE_SLOTS='4',
                   SERVE_WORKERS='1')
        if i == 0:
            env['FLAGS_fault_plan'] = plan_spec
        sup.add_role('replica%d' % i,
                     [sys.executable, _SERVE_REPLICA], env=env)
    env = dict(base_env, FLEET_ROLE='grayfail',
               FLEET_MODEL_DIR=model_dir,
               FLEET_REPLICAS=','.join(eps), FLEET_SEED=str(seed),
               FLEET_STREAMS=str(streams), FLEET_BUDGET=str(gen))
    sup.add_role('driver', [sys.executable, _FLEET_WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=budget)
    outs = [sup.output(n) for n in sorted(states)]
    try:
        if any(s in ('running', 'backoff') for s in states.values()):
            return 'hung', None, victim, plan_spec, outs
        if any(s == 'failed' for s in states.values()):
            return 'fatal', None, victim, plan_spec, outs
        result = None
        for ln in sup.output('driver').splitlines():
            if ln.startswith('RESULT '):
                result = json.loads(ln[len('RESULT '):])
        if result is None:
            return 'fatal', None, victim, plan_spec, outs
        if result['mismatches'] or result['high_bad'] or \
                result['deadline_expired']:
            return 'diverged', result, victim, plan_spec, outs
        if 'fault injection: stall' not in sup.output('replica0'):
            # the workload finished before the Nth poll: a clean run
            return 'nokill', result, victim, plan_spec, outs
        if result['gray_marks'] < 1:
            # the stall fired but the watchdog never caught it — the
            # machinery this sweep exists to gate did not engage
            return 'fatal', result, victim, plan_spec, outs
        return 'recovered', result, victim, plan_spec, outs
    finally:
        sup.stop()


def _run_disagg_seed(seed, budget, workdir, model_dir, streams=16,
                     gen=4, obs_dir=None):
    """One --disagg seed: two paged decode replicas + one paged
    prefill-tier replica + the disagg driver (tests/fleet_worker.py)
    under the Supervisor. The seeded fault lands on the prefill
    replica's SRV_PAGE_FETCH recv side — either a kill-9 (`exit`, the
    Supervisor restarts it on the same port) or a 20-40s `stall` of
    the fetch connection, which the decode tier's 2s
    FLAGS_disagg_ship_timeout turns into a ShipError and a local
    re-prefill. The fault's nth is capped at 2 because at most one
    fetch per decode replica ever reaches the wire (after the first
    ship the pages are resident and dedup short-circuits), and a
    restarted prefill replica re-counts from zero but sees no further
    fetches. Acceptance comes from the driver's RESULT: every stream
    DONE and bit-exact, and — once the fault demonstrably fired —
    failovers + local_reprefills >= 1. Returns (verdict, result,
    victim, plan_json, outs)."""
    import random

    from paddle_tpu.distributed.supervisor import Supervisor

    ports = _free_ports(3)
    eps = ['127.0.0.1:%d' % p for p in ports]
    decode_eps, prefill_ep = eps[:2], eps[2]
    rng = random.Random(('disagg', seed).__repr__())
    mode = rng.choice(['kill', 'stall'])
    victim = 'prefill0'
    rule = {'when': 'recv', 'type': 'SRV_PAGE_FETCH',
            'nth': rng.randint(1, 2)}
    if mode == 'kill':
        rule['action'] = 'exit'
    else:
        rule['action'] = 'stall'
        rule['secs'] = round(20.0 + 20.0 * rng.random(), 1)
    plan_json = json.dumps({'rules': [rule]})
    base_env = dict(os.environ)
    base_env['JAX_PLATFORMS'] = 'cpu'
    base_env.pop('XLA_FLAGS', None)
    if obs_dir:
        base_env['FLAGS_obs_flush_secs'] = '0.5'
    paged_env = {'SERVE_MODEL_DIR': model_dir, 'SERVE_SLOTS': '4',
                 'SERVE_WORKERS': '1',
                 'SERVE_PAGE_TOKENS': '4', 'SERVE_KV_PAGES': '64',
                 'SERVE_PREFILL_CHUNK': '16'}
    sup = Supervisor(max_restarts=2, backoff=0.5, log_dir=workdir,
                     obs_dir=obs_dir)
    for i, ep in enumerate(decode_eps):
        # a short ship timeout so the stall flavor converts into a
        # local re-prefill well inside the stream deadline — the flag
        # is read at decode-replica import from env
        env = dict(base_env, SERVE_ENDPOINT=ep,
                   FLAGS_disagg_ship_timeout='2.0', **paged_env)
        sup.add_role('replica%d' % i,
                     [sys.executable, _SERVE_REPLICA], env=env)
    # fixed port: a kill-9'd prefill replica rebinds the SAME endpoint
    env = dict(base_env, SERVE_ENDPOINT=prefill_ep,
               FLAGS_fault_plan=plan_json, **paged_env)
    sup.add_role('prefill0', [sys.executable, _SERVE_REPLICA], env=env)
    env = dict(base_env, FLEET_ROLE='disagg',
               FLEET_MODEL_DIR=model_dir,
               FLEET_REPLICAS=','.join(decode_eps),
               FLEET_PREFILL=prefill_ep, FLEET_SEED='0',
               FLEET_STREAMS=str(streams), FLEET_BUDGET=str(gen))
    sup.add_role('driver', [sys.executable, _FLEET_WORKER], env=env)
    sup.start()
    states = sup.wait(timeout=budget)
    outs = [sup.output(n) for n in sorted(states)]
    try:
        if any(s in ('running', 'backoff') for s in states.values()):
            return 'hung', None, victim, plan_json, outs
        if any(s == 'failed' for s in states.values()):
            return 'fatal', None, victim, plan_json, outs
        result = None
        for ln in sup.output('driver').splitlines():
            if ln.startswith('RESULT '):
                result = json.loads(ln[len('RESULT '):])
        if result is None:
            return 'fatal', None, victim, plan_json, outs
        if result['mismatches'] or result['done'] != result['submitted']:
            return 'diverged', result, victim, plan_json, outs
        fired = (sup.restarts[victim] >= 1 if mode == 'kill' else
                 'fault injection: stall' in sup.output(victim))
        if not fired:
            # the workload never reached the Nth fetch: a clean run
            return 'nokill', result, victim, plan_json, outs
        if result['failovers'] + result['local_reprefills'] < 1:
            # the fault fired but no fallback engaged — the machinery
            # this sweep exists to gate did not show up
            return 'fatal', result, victim, plan_json, outs
        return 'recovered', result, victim, plan_json, outs
    finally:
        sup.stop()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--seeds', type=int, default=None,
                    help='number of seeds to sweep (default 20, '
                         'or 3 under --quick)')
    ap.add_argument('--seed-start', type=int, default=0)
    ap.add_argument('--model', default='mlp')
    ap.add_argument('--steps', type=int, default=3)
    ap.add_argument('--trainers', type=int, default=2)
    ap.add_argument('--pservers', type=int, default=2)
    ap.add_argument('--budget', type=float, default=180.0,
                    help='per-seed wall-clock budget in seconds')
    ap.add_argument('--verbose', action='store_true',
                    help='dump worker output for non-ok seeds')
    ap.add_argument('--kill', action='store_true',
                    help='process-kill mode: seeded exit faults under '
                         'the restarting Supervisor (elastic recovery)')
    ap.add_argument('--corrupt', action='store_true',
                    help='integrity mode: seeded bit-flip (corrupt) and '
                         'poisoned-gradient (nan) plans on trainer 0')
    ap.add_argument('--mesh-kill', action='store_true',
                    help='sharded-mesh elastic recovery: kill-9 a '
                         'supervised mesh trainer (sharded checkpoints) '
                         'at a seeded step; bit-exact resume required')
    ap.add_argument('--refresh', action='store_true',
                    help='online-refresh chaos: corrupt/kill pserver 0 '
                         'while a serving process tracks its published '
                         'param versions; serving must converge to the '
                         "trainer's final digests without restarting")
    ap.add_argument('--fleet', action='store_true',
                    help='fleet serving chaos: kill-9 a serving replica '
                         'or the router driver mid-stream at a seeded '
                         'wire message; the recovered fleet must '
                         'reproduce the fault-free streams bit-exactly')
    ap.add_argument('--overload', action='store_true',
                    help='preempt-first capacity chaos: a seeded 10x '
                         'mixed-tier burst against tight paged '
                         'replicas plus a replica kill-9; requires '
                         'zero high-tier sheds, bit-exact completed '
                         'streams, and at least one preemption')
    ap.add_argument('--grayfail', action='store_true',
                    help='gray-failure chaos: replica 0 stalls its data '
                         'connection (health still passing) at a seeded '
                         'SRV_POLL; the progress watchdog must gray-mark '
                         'it, fail streams over bit-exactly, and honor '
                         'every high-tier deadline')
    ap.add_argument('--disagg', action='store_true',
                    help='disaggregated prefill/decode chaos: kill-9 or '
                         'gray-stall the prefill-tier replica at a '
                         'seeded SRV_PAGE_FETCH mid-ship; every stream '
                         'must finish bit-exact via local re-prefill')
    ap.add_argument('--mesh-serve', action='store_true',
                    help='mesh-sharded serving chaos: kill-9 a GSPMD '
                         '(SERVE_MESH_SHAPE=tp=2) replica mid-stream at '
                         'a seeded wire message; the recovered fleet '
                         'must reproduce the fault-free SINGLE-chip '
                         'stream baseline bit-exactly')
    ap.add_argument('--quick', action='store_true',
                    help='CI smoke: 3 seeds unless --seeds given, and '
                         'fatal/hung seeds fail the sweep too')
    ap.add_argument('--report', action='store_true',
                    help='run every seed with per-role observability '
                         'on, attach the metrics rollup to each row, '
                         'and write sweep_report.json (+ per-seed '
                         'chrome timelines) under --report-dir')
    ap.add_argument('--report-dir', default=None,
                    help='where --report keeps per-seed obs output '
                         '(default: a ./chaos_report.<pid> dir)')
    args = ap.parse_args(argv)
    if sum((args.kill, args.corrupt, args.mesh_kill, args.refresh,
            args.fleet, args.overload, args.grayfail, args.disagg,
            args.mesh_serve)) > 1:
        ap.error('--kill, --corrupt, --mesh-kill, --refresh, --fleet, '
                 '--overload, --grayfail, --disagg and --mesh-serve '
                 'are mutually exclusive')
    if args.seeds is None:
        args.seeds = 3 if args.quick else 20

    import random
    import tempfile

    import numpy as np

    from paddle_tpu.distributed.resilience import FaultPlan

    if args.refresh:
        # no external baseline: the trainer's OWN final-pull digests
        # (printed by online_worker) are the acceptance reference, so
        # the comparison lives inside _run_refresh_seed
        local_w = {}
    elif (args.fleet or args.overload or args.grayfail or args.disagg
          or args.mesh_serve):
        # one model for the whole sweep (every replica and every seed
        # serves the identical bytes), then — for --fleet and
        # --mesh-serve — a fault-free SINGLE-chip fleet run for the
        # bit-exact stream baseline (--overload, --grayfail and
        # --disagg need no external baseline: their drivers check
        # every stream against an in-process reference)
        import atexit
        import shutil
        fleet_root = tempfile.mkdtemp(prefix='fleet_sweep.')
        atexit.register(shutil.rmtree, fleet_root, ignore_errors=True)
        model_dir = os.path.join(fleet_root, 'model')
        build_env = dict(os.environ, FLEET_ROLE='build',
                         FLEET_MODEL_DIR=model_dir)
        build_env.pop('XLA_FLAGS', None)
        subprocess.run([sys.executable, _FLEET_WORKER], env=build_env,
                       check=True)
        if args.fleet or args.mesh_serve:
            print('baseline: fault-free fleet (single-chip) ...')
            with tempfile.TemporaryDirectory() as workdir:
                verdict, fleet_baseline, _, _, outs = _run_fleet_seed(
                    0, args.budget, workdir, model_dir, None)
            if verdict != 'ok':
                print('fleet baseline failed (%s)' % verdict)
                if args.verbose:
                    for out in outs:
                        print('  | ' +
                              '\n  | '.join(out.splitlines()[-15:]))
                return 1
        local_w = {}
    elif args.mesh_kill:
        # the mesh sweep's baseline is the same worker, fault-free —
        # acceptance is BIT-exact, so it must be the identical program,
        # not ps_worker's local_train
        mesh_steps = max(args.steps, 6)
        print('baseline: supervised mesh x %d steps ...' % mesh_steps)
        with tempfile.TemporaryDirectory() as workdir:
            verdict, local_w, _, outs = _run_mesh_seed(
                None, mesh_steps, args.budget, workdir)
        if verdict != 'ok':
            print('mesh baseline failed (%s)' % verdict)
            if args.verbose:
                for out in outs:
                    print('  | ' + '\n  | '.join(out.splitlines()[-15:]))
            return 1
    else:
        import ps_worker
        print('baseline: local %s x %d steps ...'
              % (args.model, args.steps))
        _, local_w = ps_worker.local_train(args.model, args.steps, 'sgd',
                                           args.trainers)

    report_root = None
    if args.report:
        from paddle_tpu.obs import report as obs_report
        report_root = args.report_dir or ('chaos_report.%d' % os.getpid())
        os.makedirs(report_root, exist_ok=True)

    ok_verdicts = (('ok', 'recovered', 'nokill') if args.refresh
                   else ('recovered', 'nokill')
                   if (args.kill or args.mesh_kill or args.fleet or
                       args.overload or args.grayfail or args.disagg
                       or args.mesh_serve)
                   else ('ok',))
    tally = {'ok': 0, 'recovered': 0, 'nokill': 0, 'diverged': 0,
             'fatal': 0, 'hung': 0}
    bad_seeds, rows = [], []
    for seed in range(args.seed_start, args.seed_start + args.seeds):
        t0 = time.monotonic()
        obs_dir = None
        if report_root:
            obs_dir = os.path.join(report_root, 'seed%04d' % seed)
            os.makedirs(obs_dir, exist_ok=True)
        if args.refresh:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, fmode, plan_json, outs = _run_refresh_seed(
                    seed, args.steps, args.pservers, args.budget,
                    workdir, obs_dir)
            weights = {}
            label = 'refresh/%s %s' % (fmode, plan_json)
        elif args.fleet:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, _streams, victim, plan_json, outs = \
                    _run_fleet_seed(seed, args.budget, workdir,
                                    model_dir, fleet_baseline,
                                    obs_dir=obs_dir)
            weights = {}
            label = '%s %s' % (victim, plan_json)
        elif args.mesh_serve:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, _streams, victim, plan_json, outs = \
                    _run_fleet_seed(seed, args.budget, workdir,
                                    model_dir, fleet_baseline,
                                    obs_dir=obs_dir, mesh='tp=2')
            weights = {}
            label = 'mesh(tp=2) %s %s' % (victim, plan_json)
        elif args.overload:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, result, victim, plan_json, outs = \
                    _run_overload_seed(seed, args.budget, workdir,
                                       model_dir, obs_dir=obs_dir)
            weights = {}
            label = '%s %s %s' % (victim, plan_json, json.dumps(result))
        elif args.grayfail:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, result, victim, plan_json, outs = \
                    _run_grayfail_seed(seed, args.budget, workdir,
                                       model_dir, obs_dir=obs_dir)
            weights = {}
            if result is not None:    # streams are bulky; counts only
                result = {k: v for k, v in result.items()
                          if k not in ('streams', 'states')}
            label = '%s %s %s' % (victim, plan_json, json.dumps(result))
        elif args.disagg:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, result, victim, plan_json, outs = \
                    _run_disagg_seed(seed, args.budget, workdir,
                                     model_dir, obs_dir=obs_dir)
            weights = {}
            if result is not None:    # streams are bulky; counts only
                result = {k: v for k, v in result.items()
                          if k not in ('streams', 'states')}
            label = '%s %s %s' % (victim, plan_json, json.dumps(result))
        elif args.mesh_kill:
            # kill inside the live step range; nth counts on_step calls
            kill_nth = random.Random(('mesh', seed).__repr__()).randint(
                2, mesh_steps)
            with tempfile.TemporaryDirectory() as workdir:
                verdict, weights, plan_json, outs = _run_mesh_seed(
                    kill_nth, mesh_steps, args.budget, workdir, obs_dir)
            label = 'mesh %s' % plan_json
        elif args.kill:
            with tempfile.TemporaryDirectory() as workdir:
                verdict, weights, victim, plan_json, outs = \
                    _run_kill_seed(seed, args.model, args.steps,
                                   args.trainers, args.pservers,
                                   args.budget, workdir, obs_dir)
            label = '%s %s' % (victim, plan_json)
        else:
            plan = (FaultPlan.from_corrupt_seed(seed) if args.corrupt
                    else FaultPlan.from_seed(seed))
            plan_json = label = plan.to_json()
            verdict, weights, outs = _run_seed(
                plan_json, args.model, args.steps, args.trainers,
                args.pservers, args.budget, obs_dir)
        if verdict in ok_verdicts:
            for p, lw in local_w.items():
                got = np.asarray(weights.get(p))
                if args.mesh_kill:
                    # sharded-checkpoint resume replays identical
                    # arithmetic: BIT-exact or it is a recovery bug
                    if not np.array_equal(got, np.asarray(lw)):
                        verdict = 'diverged'
                        break
                elif not np.allclose(got, np.asarray(lw),
                                     rtol=1e-4, atol=1e-5):
                    verdict = 'diverged'
                    break
        tally[verdict] += 1
        if verdict == 'diverged':
            bad_seeds.append(seed)
        row = {'seed': seed, 'verdict': verdict, 'plan': plan_json,
               'secs': round(time.monotonic() - t0, 1)}
        if obs_dir:
            # merge this seed's per-role JSONL: timeline next to the
            # obs output, nonzero rollup totals inline on the row
            try:
                _, ru = obs_report.write_report(
                    obs_dir,
                    timeline_path=os.path.join(obs_dir, 'timeline.json'),
                    rollup_path=os.path.join(obs_dir, 'rollup.json'))
                row['rollup'] = {n: v for n, v in
                                 sorted(ru['totals'].items()) if v}
            except Exception as e:   # noqa: BLE001 — report best-effort
                row['rollup_error'] = str(e)
        rows.append(row)
        print('seed %4d  %-9s  %5.1fs  %s'
              % (seed, verdict, time.monotonic() - t0, label))
        if args.verbose and verdict not in ok_verdicts:
            for out in outs:
                print('  | ' + '\n  | '.join(out.splitlines()[-15:]))

    total = sum(tally.values())
    print('\nswept %d seeds: %d ok, %d recovered, %d nokill, '
          '%d diverged, %d fatal, %d hung'
          % (total, tally['ok'], tally['recovered'], tally['nokill'],
             tally['diverged'], tally['fatal'], tally['hung']))
    if report_root:
        mode = ('refresh' if args.refresh
                else 'mesh-serve' if args.mesh_serve
                else 'fleet' if args.fleet
                else 'overload' if args.overload
                else 'grayfail' if args.grayfail
                else 'disagg' if args.disagg
                else 'mesh-kill' if args.mesh_kill
                else 'kill' if args.kill
                else 'corrupt' if args.corrupt else 'fault')
        report_path = os.path.join(report_root, 'sweep_report.json')
        with open(report_path, 'w') as f:
            json.dump({'mode': mode, 'model': args.model,
                       'steps': args.steps, 'trainers': args.trainers,
                       'pservers': args.pservers, 'tally': tally,
                       'rows': rows}, f, indent=2)
        print('sweep report -> %s (per-seed timelines under %s/seedNNNN)'
              % (report_path, report_root))
    if bad_seeds:
        print('DIVERGED seeds (reproduce with --seed-start N --seeds 1 '
              '--verbose): %s' % bad_seeds)
        return 1
    if args.quick and (tally['fatal'] or tally['hung']):
        print('QUICK sweep failed: %d fatal, %d hung (quick mode gates '
              'on every non-ok outcome)' % (tally['fatal'], tally['hung']))
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
