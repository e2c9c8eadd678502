"""Benchmark suite: 5 models x 3 execution modes.

Breadth analog of the reference harness (benchmark/fluid/
fluid_benchmark.py:116-312: 5 models x local/parallel/dist) for this
framework. The driver-facing headline stays bench.py (ResNet +
Transformer on the real chip); this suite demonstrates every model
family running under every execution engine:

  models: mnist | resnet | vgg | stacked_lstm | transformer
  modes:  local      (Executor, 1 device)
          parallel   (ParallelExecutor over all visible devices)
          dist N     (N trainer processes, collective DP — subprocess
                      localhost, the test_dist_base.py pattern)
          pserver    (N trainers + 2 parameter servers via the
                      DistributeTranspiler — the reference harness's
                      pserver update method)

Usage:
  python tools/bench_suite.py                     # quick sweep, tiny shapes
  python tools/bench_suite.py --model resnet --mode parallel --steps 20
  python tools/bench_suite.py --full              # benchmark shapes; fails
                                                  # without a TPU

Prints one row per (model, mode): samples/sec + final loss.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _build(model, full):
    import paddle_tpu as fluid
    from paddle_tpu.models import (mnist, resnet, vgg, transformer,
                                   stacked_lstm, alexnet, googlenet)
    d = {}
    if model == 'mnist':
        img = fluid.layers.data(name='img', shape=[1, 28, 28],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        _, loss, _ = mnist.train_network(img, label)
        feed = lambda rng, bs: {
            'img': rng.rand(bs, 1, 28, 28).astype('float32'),
            'label': rng.randint(0, 10, (bs, 1)).astype('int64')}
        bs = 64 if not full else 256
    elif model in ('resnet', 'vgg', 'alexnet', 'googlenet'):
        # alexnet's stride-4 11x11 stem and googlenet's pool chain
        # need more spatial extent than the 32px cifar shapes
        small_hw = {'alexnet': 67, 'googlenet': 64}.get(model, 32)
        hw, classes = (224, 1000) if full else (small_hw, 10)
        img = fluid.layers.data(name='img', shape=[3, hw, hw],
                                dtype='float32')
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        mod = {'resnet': resnet, 'vgg': vgg, 'alexnet': alexnet,
               'googlenet': googlenet}[model]
        kw = {'depth': 50} if (model == 'resnet' and full) else (
            {'depth': 18} if model == 'resnet' else {})
        if model == 'googlenet' and not full:
            kw = {'aux_heads': False}   # aux pool needs >=5 spatial at
            #                             stage 4 (112px+); main head only
        _, loss, _ = mod.train_network(img, label, class_dim=classes,
                                       **kw)
        feed = lambda rng, bs: {
            'img': rng.rand(bs, 3, hw, hw).astype('float32'),
            'label': rng.randint(0, classes, (bs, 1)).astype('int64')}
        bs = 8 if not full else 256
    elif model == 'stacked_lstm':
        T, vocab = (16, 1000) if not full else (128, 30000)
        data = fluid.layers.data(name='words', shape=[1], dtype='int64',
                                 lod_level=1)
        label = fluid.layers.data(name='label', shape=[1], dtype='int64')
        kw = {} if full else {'emb_dim': 64, 'hid_dim': 64}
        _, loss, _ = stacked_lstm.train_network(data, label, vocab, **kw)

        def feed(rng, bs):
            ids = rng.randint(1, vocab, (bs, T, 1)).astype('int64')
            lens = np.full((bs,), T, 'int32')
            return {'words': (ids, lens),
                    'label': rng.randint(0, 2, (bs, 1)).astype('int64')}
        bs = 8 if not full else 64
    elif model in ('transformer', 'longcontext'):
        sp = model == 'longcontext'   # sp-ring attention over the mesh
        cfg = transformer.TransformerConfig(
            vocab=32768 if full else 256,
            dim=(1024 if sp else 2048) if full else 64,
            heads=(8 if sp else 16) if full else 4,
            layers=(4 if sp else 12) if full else 2,
            ffn=(4096 if sp else 8192) if full else 128,
            max_len=(8192 if sp else 512) if full else (64 if sp else 16),
            use_tp=False, use_sp=sp, ring_attention=sp)
        tokens = fluid.layers.data(name='tokens',
                                   shape=[cfg.max_len, 1], dtype='int64')
        labels = fluid.layers.data(name='labels',
                                   shape=[cfg.max_len, 1], dtype='int64')
        _, loss = transformer.train_network(tokens, labels, cfg)

        def feed(rng, bs):
            t = rng.randint(0, cfg.vocab,
                            (bs, cfg.max_len, 1)).astype('int64')
            return {'tokens': t, 'labels': np.roll(t, -1, 1)}
        bs = 2 if not full else (2 if sp else 8)
    else:
        raise SystemExit('unknown model %r' % model)
    return loss, feed, bs


def _fresh_build(model, full):
    """Reset naming + default programs, build the model + Adam, run
    startup; shared by run_one and run_scaling so the two modes cannot
    drift apart. Returns (loss, feed_fn, bs, scope, exe)."""
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    unique_name.switch()
    fluid.framework.switch_main_program(fluid.framework.Program())
    fluid.framework.switch_startup_program(fluid.framework.Program())
    with fluid.program_guard(fluid.default_main_program(),
                             fluid.default_startup_program()):
        loss, feed_fn, bs = _build(model, full)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.TPUPlace() if full else fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(fluid.default_startup_program())
    return loss, feed_fn, bs, scope, exe


def run_one(model, mode, steps, full, quick=False):
    import paddle_tpu as fluid
    import jax
    if quick:
        # perf-gate feed: record through the obs perf observatory so
        # the row carries compile/HBM columns alongside throughput
        from paddle_tpu.obs import telemetry, perf
        telemetry.reset()
        telemetry.enable()
        perf._reset_for_tests()
    loss, feed_fn, bs, scope, exe = _fresh_build(model, full)
    rng = np.random.RandomState(0)
    if mode == 'parallel':
        runner = fluid.ParallelExecutor(
            use_cuda=full, loss_name=loss.name,
            main_program=fluid.default_main_program(), scope=scope)
        bs *= max(len(jax.devices()), 1)
        run = lambda f: runner.run(fetch_list=[loss.name], feed=f)
    else:
        run = lambda f: exe.run(fluid.default_main_program(), feed=f,
                                fetch_list=[loss], scope=scope)
    lv = run(feed_fn(rng, bs))     # warm/compile
    t0 = time.perf_counter()
    for _ in range(steps):
        lv = run(feed_fn(rng, bs))
    dt = time.perf_counter() - t0
    row = {'model': model, 'mode': mode,
           'samples_per_sec': round(bs * steps / dt, 2),
           'loss': round(float(np.asarray(lv[0]).mean()), 4)}
    if quick:
        snap = telemetry.snapshot()
        row['compile_ms'] = round(
            snap['hists']['xla.compile_latency']['sum'] * 1e3, 1)
        row['hbm_peak'] = int(snap['gauges']['hbm.watermark_bytes'])
        telemetry.disable(final_flush=False)
        telemetry.reset()
        if model == 'transformer':
            # mesh-sharded serving leg (serve_bench --quick --mesh):
            # stamps the SPMD decode throughput + per-chip numbers the
            # perf gate tracks, and the mesh axis spec they ran under
            mesh = _mesh_quick()
            if mesh.get('mesh_tokens_per_sec'):
                row['mesh_shape'] = mesh.get('mesh_shape', '')
                for key in ('mesh_tokens_per_sec',
                            'mesh_tokens_per_sec_per_chip',
                            'mesh_hbm_per_chip_mb'):
                    row[key] = mesh[key]
    elif model == 'transformer' and mode == 'local':
        # subprocess extra — skipped under --quick to keep the gate
        # feed fast
        serving = _serving_quick()
        if serving.get('infer_decode_speedup'):
            row['decode_speedup'] = serving['infer_decode_speedup']
        if serving.get('refresh_p99_ratio'):
            row['refresh_p99_ratio'] = serving['refresh_p99_ratio']
        if serving.get('fleet_tokens_per_sec'):
            row['fleet_tokens_per_sec'] = serving['fleet_tokens_per_sec']
        if serving.get('fleet_p99_ttft_ms'):
            row['fleet_p99_ttft_ms'] = serving['fleet_p99_ttft_ms']
        if serving.get('disagg_p99_ttft_ms'):
            row['disagg_p99_ttft_ms'] = serving['disagg_p99_ttft_ms']
        if serving.get('fleet_prefix_hit_rate'):
            row['fleet_prefix_hit_rate'] = \
                serving['fleet_prefix_hit_rate']
    return row


def run_scaling(model, steps, full, bn_local_stats=False,
                zero3=False, sp_ring=False):
    """Weak-scaling + collective audit (VERDICT round-4 #4; the
    BASELINE 'ParallelExecutor scaling eff' metric's measurement path;
    reference analog: benchmark/fluid/fluid_benchmark.py:198
    train_parallel).

    On the 8-virtual-CPU-device mesh the host's total compute is fixed,
    so the honest weak-scaling proxy is: run the SAME global batch
    (B*n) on 1 device and sharded over n devices — the ratio isolates
    partitioning + collective overhead from compute. Also dumps the
    compiled HLO of the n=8 step and audits its collectives: count,
    bytes, op types, and whether per-gradient all-reduces coalesced."""
    import re
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import unique_name
    devices = jax.devices()
    sizes = [n for n in (1, 2, 4, 8) if n <= len(devices)]
    out = {'model': model, 'mode': 'scaling', 'points': []}
    strategy_for = (lambda n: None)
    if zero3:
        # ZeRO-3 sharded params (parallel/strategy.py sharded_params):
        # the audit shows the gather-on-use / reduce-scatter pattern
        # and the per-device parameter shards. Validate BEFORE any
        # global flag mutation so an error leaks no state.
        from paddle_tpu.parallel import DistributedStrategy
        if len(devices) < 2:
            raise RuntimeError('--zero3 needs a multi-device mesh '
                               '(only %d device visible) — the label '
                               'must not ship unexercised'
                               % len(devices))
        out['zero3_sharded_params'] = True
        strategy_for = (lambda n: DistributedStrategy(
            dp=n, sharded_params=True) if n > 1 else None)
    if sp_ring:
        # sequence parallelism: the SAME (batch, sequence) is sharded
        # over the sp ring, so — unlike dp weak scaling — the global
        # batch is NOT inflated; the n>1 points isolate ring
        # partitioning + collective-permute overhead, and the audit
        # certifies the ring's collective pattern from the compiled HLO
        from paddle_tpu.parallel import DistributedStrategy
        if model != 'longcontext':
            raise RuntimeError('--sp-ring applies to the longcontext '
                               'model (got %r)' % model)
        if zero3:
            # each branch overwrites strategy_for — combining would
            # ship a label whose strategy never ran
            raise RuntimeError('--zero3 and --sp-ring are mutually '
                               'exclusive scaling strategies')
        out['sp_ring'] = True
        if not full:
            # On a ONE-HOST virtual mesh the ring's scan-of-ppermute
            # serializes per step (~50x measured vs the n=1
            # plain-attention point), so unlike the dp proxy the sp
            # step points carry no predictive signal — the compiled-HLO
            # collective audit (ring = collective-permutes, grads = one
            # coalesced all-reduce) is this mode's artifact; per-step
            # ring cost on real ICI is bounded by the ppermute bytes
            # the audit reports. Real-hardware --full runs keep their
            # step points uncaveated.
            out['virtual_mesh_caveat'] = (
                'sp step points are a one-host serialization artifact; '
                'the collective audit is the signal (COVERAGE.md '
                'divergences)')
        strategy_for = (lambda n: DistributedStrategy(sp=n)
                        if n > 1 else None)
    prior_bn_local = fluid.flags.get_flag('bn_local_stats')
    prior_flash = fluid.flags.get_flag('use_flash_attention')
    if sp_ring and not full:
        # On the virtual CPU mesh the ring's per-block flash kernel
        # would run in Pallas INTERPRET mode (~100x slow) while the
        # n=1 baseline runs XLA — route the ring through the exact
        # XLA per-block path so the scaling points compare like with
        # like. The collective audit is unaffected (the ring's permute
        # pattern is identical in both arms).
        fluid.flags.set_flags({'FLAGS_use_flash_attention': False})
    if bn_local_stats:
        out['bn_local_stats'] = True
        fluid.flags.set_flags({'FLAGS_bn_local_stats': True})
    try:
        audit_exe = None
        for n in sizes:
            loss, feed_fn, bs, scope, exe = _fresh_build(model, full)
            pe = fluid.ParallelExecutor(
                use_cuda=full, loss_name=loss.name,
                main_program=fluid.default_main_program(), scope=scope,
                devices=devices[:n], strategy=strategy_for(n))
            rng = np.random.RandomState(0)
            # dp weak scaling: SAME global batch at every n. sp: the
            # sequence (not the batch) is what shards — batch stays bs.
            global_bs = bs if sp_ring else bs * sizes[-1]
            f = feed_fn(rng, global_bs)
            pe.run(fetch_list=[loss.name], feed=f)     # compile
            t0 = time.perf_counter()
            for _ in range(steps):
                lv = pe.run(fetch_list=[loss.name], feed=f)
            dt = (time.perf_counter() - t0) / steps
            out['points'].append({'devices': n, 'step_ms': round(dt * 1e3, 2)})
            if n == sizes[-1]:
                audit_exe = pe
        base = out['points'][0]['step_ms']
        for p in out['points']:
            p['efficiency_vs_1dev'] = round(base / p['step_ms'], 3)

        # ---- collective audit on the widest mesh ----
        if audit_exe is not None:
            from paddle_tpu.profiler import collective_audit
            colls = collective_audit(audit_exe.compiled_hlo_texts())
            audit = {}
            for kind, sizes_b in colls.items():
                audit[kind] = {
                    'count': len(sizes_b),
                    'total_mb': round(sum(sizes_b) / 1e6, 3),
                    'largest_mb': round(max(sizes_b) / 1e6, 3)}
            out['collective_audit'] = audit
            params = fluid.default_main_program().global_block() \
                .all_parameters()
            param_mb = sum(int(np.prod(p.shape)) for p in params) * 4 / 1e6
            ar = colls.get('all-reduce', [])
            audit['n_trainable_params'] = len(params)
            audit['param_mb'] = round(param_mb, 3)
            # size-aware coalescing check: count only GRADIENT-SCALE
            # all-reduces (>=1% of param bytes — filters BN-stat syncs),
            # then require few instructions carrying most of the bytes.
            # A max-only test would call a model with one dominant param
            # (a vocab embedding) coalesced even when every grad has its
            # own all-reduce.
            big = [b for b in ar if b >= 0.01 * param_mb * 1e6]
            audit['grad_allreduce_coalesced'] = bool(big) and (
                len(big) <= max(1, len(params) // 8)
                and sum(big) / 1e6 >= 0.5 * param_mb)
    finally:
        fluid.flags.set_flags({'FLAGS_bn_local_stats': prior_bn_local,
                               'FLAGS_use_flash_attention': prior_flash})
    return out


def run_dist(model, n, steps, full):
    """N-trainer collective DP via subprocess localhost."""
    import socket
    s = socket.socket()
    s.bind(('127.0.0.1', 0))
    port = s.getsockname()[1]
    s.close()
    eps = ','.join('127.0.0.1:%d' % (port + i) for i in range(n))
    procs = []
    for i in range(n):
        env = dict(os.environ)
        env.update({'PADDLE_TRAINERS_NUM': str(n),
                    'PADDLE_TRAINER_ID': str(i),
                    'PADDLE_TRAINER_ENDPOINTS': eps,
                    'BENCH_SUITE_WORKER': '1',
                    'BENCH_SUITE_MODEL': model,
                    'BENCH_SUITE_STEPS': str(steps)})
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs = [p.communicate(timeout=600)[0] for p in procs]
    for p, out in zip(procs, outs):
        if p.returncode != 0:
            raise RuntimeError('dist worker failed:\n' + out[-2000:])
    row = json.loads([ln for ln in outs[0].splitlines()
                      if ln.startswith('{')][-1])
    row['mode'] = 'dist%d' % n
    return row


_TRANSPORT_QUICK = [None]   # dist_bench --quick, measured at most once


def _transport_quick():
    """Headline serial-vs-pipelined RPC speedup (tools/dist_bench.py
    --quick: 160 vars x 1KiB across 2 pservers) stamped onto every
    pserver-mode row; one subprocess, cached across models."""
    if _TRANSPORT_QUICK[0] is None:
        try:
            env = dict(os.environ, JAX_PLATFORMS='cpu')
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              'dist_bench.py'), '--quick'],
                capture_output=True, text=True, timeout=300, env=env)
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith('{') and '"summary"' in ln][-1]
            _TRANSPORT_QUICK[0] = json.loads(line)['speedup']
        except Exception:   # noqa: BLE001 — a bench extra, never fatal
            _TRANSPORT_QUICK[0] = 0.0
    return _TRANSPORT_QUICK[0]


_MESH_QUICK = [None]        # serve_bench --quick --mesh, at most once


def _mesh_quick():
    """Mesh-sharded serving headline (tools/serve_bench.py --quick
    --mesh): one GSPMD SPMD decode program over a tp=2 mesh vs the
    same paged pool single-chip, bit-exact checked in the bench
    itself. Stamped onto the transformer --quick row so perf_gate
    tracks mesh_tokens_per_sec / _per_chip / mesh_hbm_per_chip_mb.
    One subprocess, cached across invocations; {} on any failure."""
    if _MESH_QUICK[0] is None:
        try:
            env = dict(os.environ, JAX_PLATFORMS='cpu')
            # let the child set its own multi-device host override —
            # it must land before the child's jax backend initializes
            env.pop('XLA_FLAGS', None)
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              'serve_bench.py'), '--quick', '--mesh'],
                capture_output=True, text=True, timeout=900, env=env)
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith('{') and '"summary"' in ln][-1]
            _MESH_QUICK[0] = json.loads(line)
        except Exception:   # noqa: BLE001 — a bench extra, never fatal
            _MESH_QUICK[0] = {}
    return _MESH_QUICK[0]


_SERVING_QUICK = [None]     # serve_bench --quick, measured at most once


def _serving_quick():
    """Headline serving numbers (tools/serve_bench.py --quick
    --refresh --fleet --spec --disagg) stamped onto the
    transformer local-mode row: the cached-vs-recompute decode
    speedup, the online-refresh tail cost (refresh_p99_ratio — token
    p99 with a live ParamSubscriber install loop over the undisturbed
    p99), the fleet leg (fleet_tokens_per_sec / fleet_p99_ttft_ms
    through a FleetRouter over 2 replica subprocesses — perf_gate
    infers the direction from each suffix), the speculative-decoding
    A/B (spec_tokens_per_sec / spec_accept_rate vs plain paged decode
    at equal HBM), and the disaggregated prefill/decode A/B
    (disagg_p99_ttft_ms / fleet_prefix_hit_rate — a shared-prefix
    burst through a KV-page-shipping prefill tier vs colocated). One
    subprocess, cached across invocations; {} on any failure."""
    if _SERVING_QUICK[0] is None:
        try:
            env = dict(os.environ, JAX_PLATFORMS='cpu')
            out = subprocess.run(
                [sys.executable,
                 os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              'serve_bench.py'), '--quick', '--refresh',
                 '--fleet', '--spec', '--disagg'],
                capture_output=True, text=True, timeout=900, env=env)
            line = [ln for ln in out.stdout.splitlines()
                    if ln.startswith('{') and '"summary"' in ln][-1]
            _SERVING_QUICK[0] = json.loads(line)
        except Exception:   # noqa: BLE001 — a bench extra, never fatal
            _SERVING_QUICK[0] = {}
    return _SERVING_QUICK[0]


def run_pserver(model, n_trainers, steps, full):
    """N trainers + 2 pservers via the DistributeTranspiler (the
    reference fluid_benchmark.py's --update_method pserver)."""
    import socket
    socks = []
    for _ in range(2):
        so = socket.socket()
        so.bind(('127.0.0.1', 0))
        socks.append(so)
    ports = [so.getsockname()[1] for so in socks]
    for so in socks:        # hold all before freeing any: two bind(0)
        so.close()          # calls can otherwise return the same port
    eps = ','.join('127.0.0.1:%d' % p for p in ports)
    procs = []

    def spawn(role, extra):
        env = dict(os.environ)
        env.update({'BENCH_SUITE_PS_WORKER': '1',
                    'BENCH_SUITE_MODEL': model,
                    'BENCH_SUITE_STEPS': str(steps),
                    'PS_ROLE': role, 'PS_ENDPOINTS': eps,
                    'PS_TRAINERS': str(n_trainers)})
        env.update(extra)
        return subprocess.Popen(
            [sys.executable, os.path.abspath(__file__)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)

    for i in range(2):
        procs.append(spawn('pserver', {'PS_PSERVER_ID': str(i)}))
    time.sleep(1.0)
    trainers = [spawn('trainer', {'PS_TRAINER_ID': str(i)})
                for i in range(n_trainers)]
    try:
        outs = [p.communicate(timeout=600)[0] for p in trainers]
        # diagnose trainer failures FIRST: a dead trainer never sends
        # COMPLETE, so the pservers would hang forever
        for p, out in zip(trainers, outs):
            if p.returncode != 0:
                raise RuntimeError('pserver-mode trainer failed:\n'
                                   + out[-2000:])
        for p in procs:
            out, _ = p.communicate(timeout=60)
            if p.returncode not in (0, None):
                raise RuntimeError('pserver failed:\n' + out[-2000:])
    finally:
        for p in procs + trainers:
            if p.poll() is None:
                p.kill()
    row = json.loads([ln for ln in outs[0].splitlines()
                      if ln.startswith('{')][-1])
    row['samples_per_sec'] = round(
        row['samples_per_sec'] * n_trainers, 2)
    row['mode'] = 'pserver%d' % n_trainers
    spd = _transport_quick()
    if spd:
        row['transport_speedup'] = spd
    return row


def _pserver_worker():
    import jax
    jax.config.update('jax_platforms', 'cpu')
    import paddle_tpu as fluid
    model = os.environ['BENCH_SUITE_MODEL']
    steps = int(os.environ['BENCH_SUITE_STEPS'])
    role = os.environ['PS_ROLE']
    eps = os.environ['PS_ENDPOINTS']
    trainers = int(os.environ['PS_TRAINERS'])
    trainer_id = int(os.environ.get('PS_TRAINER_ID', 0))
    with fluid.program_guard(fluid.default_main_program(),
                             fluid.default_startup_program()):
        loss, feed_fn, bs = _build(model, False)
        # pserver path: plain SGD (the transpiler moves optimize ops
        # server-side)
        fluid.optimizer.SGD(1e-3).minimize(loss)
    t = fluid.DistributeTranspiler()
    t.transpile(trainer_id, pservers=eps, trainers=trainers,
                sync_mode=True)
    exe = fluid.Executor(fluid.CPUPlace())
    if role == 'pserver':
        ep = eps.split(',')[int(os.environ['PS_PSERVER_ID'])]
        main_prog, startup = t.get_pserver_programs(ep)
        exe.run(startup)
        exe.run(main_prog)
        return
    exe.run(t.get_trainer_startup_program())
    prog = t.get_trainer_program()
    rng = np.random.RandomState(trainer_id)
    lv = exe.run(prog, feed=feed_fn(rng, bs), fetch_list=[loss])
    t0 = time.perf_counter()
    for _ in range(steps):
        lv = exe.run(prog, feed=feed_fn(rng, bs), fetch_list=[loss])
    dt = time.perf_counter() - t0
    print(json.dumps({'model': model,
                      'samples_per_sec': round(bs * steps / dt, 2),
                      'loss': round(float(np.asarray(lv[0]).mean()), 4)}),
          flush=True)
    exe.close()


def _dist_worker():
    os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')
                               + ' --xla_force_host_platform_device_count=2')
    import jax
    jax.config.update('jax_platforms', 'cpu')
    model = os.environ['BENCH_SUITE_MODEL']
    steps = int(os.environ['BENCH_SUITE_STEPS'])
    import paddle_tpu as fluid
    with fluid.program_guard(fluid.default_main_program(),
                             fluid.default_startup_program()):
        loss, feed_fn, bs = _build(model, False)
        fluid.optimizer.Adam(1e-3).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    pe = fluid.ParallelExecutor(
        use_cuda=False, loss_name=loss.name,
        main_program=fluid.default_main_program(), scope=scope,
        num_trainers=int(os.environ['PADDLE_TRAINERS_NUM']),
        trainer_id=int(os.environ['PADDLE_TRAINER_ID']))
    with fluid.scope_guard(scope):
        exe.run(fluid.default_startup_program(), scope=scope)
    rng = np.random.RandomState(0)
    lv = pe.run(fetch_list=[loss.name], feed=feed_fn(rng, bs))
    t0 = time.perf_counter()
    for _ in range(steps):
        lv = pe.run(fetch_list=[loss.name], feed=feed_fn(rng, bs))
    dt = time.perf_counter() - t0
    n = int(os.environ['PADDLE_TRAINERS_NUM'])
    print(json.dumps({'model': model,
                      'samples_per_sec': round(bs * steps * n / dt, 2),
                      'loss': round(float(np.asarray(lv[0]).mean()), 4)}),
          flush=True)


MODELS = ['mnist', 'resnet', 'vgg', 'alexnet', 'googlenet',
          'stacked_lstm', 'transformer', 'longcontext']


def main():
    if os.environ.get('BENCH_SUITE_PS_WORKER'):
        _pserver_worker()
        return
    if os.environ.get('BENCH_SUITE_WORKER'):
        _dist_worker()
        return
    ap = argparse.ArgumentParser()
    ap.add_argument('--model', choices=MODELS + ['all'], default='all')
    ap.add_argument('--mode', choices=['local', 'parallel', 'dist',
                                       'pserver', 'scaling', 'all'],
                    default='all')
    ap.add_argument('--dist-trainers', type=int, default=2)
    ap.add_argument('--steps', type=int, default=5)
    ap.add_argument('--full', action='store_true',
                    help='benchmark shapes; needs a TPU and fails '
                         'without one')
    ap.add_argument('--bn-local-stats', action='store_true',
                    help='scaling mode: per-device BN statistics '
                         '(FLAGS_bn_local_stats — reference semantics)')
    ap.add_argument('--zero3', action='store_true',
                    help='scaling mode: ZeRO-3 sharded_params strategy')
    ap.add_argument('--sp-ring', action='store_true',
                    help='scaling mode: sequence-parallel ring '
                         'attention over the mesh (longcontext model)')
    ap.add_argument('--quick', action='store_true',
                    help='fast perf-gate feed: local mode on a small '
                         'model set, obs-gauge compile_ms/hbm_peak '
                         'stamped into each row, slow subprocess '
                         'extras skipped (tools/perf_gate.py '
                         '--run-suite consumes this)')
    ap.add_argument('--json', action='store_true',
                    help='print the full row list as one JSON array '
                         'on the last stdout line')
    args = ap.parse_args()
    if args.full:
        from paddle_tpu.obs import perf
        print(json.dumps(dict(perf.require_tpu(), mode='device')),
              flush=True)
    else:
        os.environ.setdefault(
            'XLA_FLAGS', '--xla_force_host_platform_device_count=8')
        import jax
        jax.config.update('jax_platforms', 'cpu')
    models = MODELS if args.model == 'all' else [args.model]
    modes = (['local', 'parallel', 'dist', 'pserver']
             if args.mode == 'all' else [args.mode])
    if args.quick:
        if args.model == 'all':
            models = ['mnist', 'transformer']
        if args.mode == 'all':
            modes = ['local']
    rows = []
    for model in models:
        for mode in modes:
            try:
                if mode == 'scaling':
                    row = run_scaling(model, args.steps, args.full,
                                      bn_local_stats=args.bn_local_stats,
                                      zero3=args.zero3,
                                      sp_ring=args.sp_ring)
                elif mode == 'pserver':
                    row = run_pserver(model, args.dist_trainers,
                                      args.steps, args.full)
                elif mode == 'dist':
                    row = run_dist(model, args.dist_trainers, args.steps,
                                   args.full)
                else:
                    row = run_one(model, mode, args.steps, args.full,
                                  quick=args.quick)
            except Exception as e:   # noqa: BLE001 — suite keeps going
                row = {'model': model, 'mode': mode,
                       'error': str(e)[:120]}
            rows.append(row)
            print(json.dumps(row), flush=True)
    ok = sum('error' not in r for r in rows)
    print('# %d/%d configurations ran' % (ok, len(rows)))
    if args.json:
        print(json.dumps(rows), flush=True)


if __name__ == '__main__':
    main()
