"""Serving benchmark: KV-cached decode vs full-prefix recompute.

Measures what paddle_tpu/serving/ buys on a decoder-only LM:

  recompute   the pre-serving decode loop — one full T-prefix forward
              per generated token through the plain AnalysisPredictor
              (O(T) work per token)
  cached      PagedDecodePredictor decode_step over the K/V page pool
              (O(1) per token), swept across slot-pool sizes: each
              batch size is its own transpiled decode program, so the
              row reflects a pool actually compiled at that width
  engine      ServingEngine end-to-end at the widest pool: continuous
              batching with per-request TTFT, driven by a burst of
              concurrent submissions

Prints one JSON row per configuration (infer_decode_* keys, the
bench.py naming) and an acceptance summary row with the cached vs
recompute speedup at full context. serving.* telemetry flows into the
obs registry; run under FLAGS_obs_dir to export it for
tools/obs_report.py.

Usage:
  python tools/serve_bench.py               # CPU-sized sweep, bs 1..64
  python tools/serve_bench.py --quick       # one tiny shape (CI smoke)
  python tools/serve_bench.py --full        # L4/D1024/T512; needs a TPU
                                            # and fails without one
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _replica_env():
    """Environment of a serve_replica.py child: the CPU. (--full, whose
    parent holds the chip, refuses the legs that start replicas.)"""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('XLA_FLAGS', None)
    return env


def _build_predictor(cfg):
    """Train-free LM -> save_inference_model -> AnalysisPredictor."""
    import paddle_tpu as fluid
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    from paddle_tpu.models import transformer as tfm
    main_prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_prog, startup):
        tokens = fluid.layers.data(
            'tokens', shape=[1, cfg.max_len, 1], dtype='int64',
            append_batch_size=False)
        logits = tfm.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.TPUPlace())
    scope = fluid.Scope()
    with tempfile.TemporaryDirectory() as tmp:
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(tmp, ['tokens'], [logits],
                                          exe, main_program=main_prog)
        return AnalysisPredictor(AnalysisConfig(tmp))


def _recompute_tokens_per_sec(pred, cfg, iters):
    """One next-token per full-prefix forward (the baseline a user
    without serving/ would run): tokens/s at context T."""
    rng = np.random.RandomState(0)
    toks = rng.randint(0, cfg.vocab, (1, cfg.max_len, 1)).astype('int64')
    pred.run([toks])
    pred.run([toks])
    t0 = time.perf_counter()
    for _ in range(iters):
        pred.run([toks])
    dt = (time.perf_counter() - t0) / iters
    return 1.0 / dt, dt


def _cached_tokens_per_sec(pred, cfg, slots, iters):
    """Steady-state decode over a full pool of `slots` lanes, each
    stream one token short of its window, the step appending the last.
    Returns (tokens/s, step_ms, prefill_ms)."""
    from paddle_tpu.flags import get_flag
    rng = np.random.RandomState(0)
    pages_per_slot = -(-cfg.max_len // get_flag('serving_page_tokens'))
    # a page more a lane than its window: the step's first append forks
    # the tail page that the prefix cache shares
    dec = pred.prepare_decoding(slots=slots,
                                kv_pages=slots * (pages_per_slot + 1) + 1,
                                prefill_chunk=cfg.max_len)
    t0 = time.perf_counter()
    for s in range(slots):
        dec.prefill([rng.randint(0, cfg.vocab, cfg.max_len - 1)], [s])
    prefill_ms = (time.perf_counter() - t0) * 1e3 / slots
    toks = rng.randint(0, cfg.vocab, slots).astype('int64')
    pos = np.full((slots,), cfg.max_len - 1, 'int32')
    dec.decode_step(toks, pos)      # compile
    dec.decode_step(toks, pos)
    t0 = time.perf_counter()
    for _ in range(iters):
        dec.decode_step(toks, pos)
    dt = (time.perf_counter() - t0) / iters
    stats = dec.jit_cache_stats()
    # prefill + decode + the page copy program (with decode's first step)
    assert stats['compiled_segments'] == 3, stats
    return slots / dt, dt * 1e3, prefill_ms


def _engine_leg(pred, cfg, slots, n_requests, new_tokens):
    """End-to-end ServingEngine burst: n_requests submitted at once,
    TTFT and completion tokens/s measured from the request records."""
    from paddle_tpu.serving import ServingEngine
    rng = np.random.RandomState(1)
    dec = pred.prepare_decoding(slots=slots)
    prompts = [rng.randint(0, cfg.vocab, max(1, cfg.max_len // 2))
               for _ in range(n_requests)]
    # compile both programs outside the measured window, then drop the
    # warmup state — TTFT should price admission + prefill, not XLA
    dec.generate(prompts[0], 2)
    dec.reset()
    t0 = time.perf_counter()
    with ServingEngine(dec) as eng:
        reqs = [eng.submit(p, max_new_tokens=new_tokens)
                for p in prompts]
        for r in reqs:
            r.result(600)
    wall = time.perf_counter() - t0
    ttfts = [r.first_token_at - r.submitted_at for r in reqs]
    total = sum(len(r.tokens) for r in reqs)
    return {'requests': n_requests, 'slots': slots,
            'engine_tokens_per_sec': round(total / wall, 2),
            'ttft_p50_ms': round(sorted(ttfts)[len(ttfts) // 2] * 1e3, 1),
            'ttft_max_ms': round(max(ttfts) * 1e3, 1)}


def _refresh_leg(pred, cfg, slots, n_requests, new_tokens):
    """Online-refresh cost leg: the SAME engine burst twice — once
    undisturbed, once with a live ParamSubscriber installing a new
    param version every ~50 ms (in-process pserver publishing rounds)
    — and the per-token latency p50/p99 + tokens/s for both.
    refresh_p99_ratio (refresh p99 / baseline p99) is the headline:
    how much tail a concurrent refresh loop costs a decode stream."""
    import threading

    from paddle_tpu.distributed.param_service import ParameterService
    from paddle_tpu.distributed.rpc import PSClient, PSServer
    from paddle_tpu.obs import telemetry
    from paddle_tpu.online import ParamSubscriber
    from paddle_tpu.serving import ServingEngine

    rng = np.random.RandomState(3)
    dec = pred.prepare_decoding(slots=slots)
    prompts = [rng.randint(0, cfg.vocab, max(1, cfg.max_len // 2))
               for _ in range(n_requests)]
    dec.generate(prompts[0], 2)         # compile outside the window

    # in-process pserver shard hosting the predictor's own params: a
    # refresh pulls + installs the full model, decode output unchanged
    params = {n: np.asarray(dec._weight_scope.find_var(n))
              for n in dec.param_names()}
    svc = ParameterService(
        num_trainers=1, sync_mode=True,
        get_param=lambda n: params[n], run_round=lambda merged: None,
        rpc_deadline=60.0, param_names=sorted(params))
    srv = PSServer('127.0.0.1:0', svc)
    sthread = threading.Thread(target=srv.serve_forever, daemon=True)
    sthread.start()

    def burst(eng, min_wall=0.35):
        # loop the burst until min_wall so the refresh loop gets to
        # land several installs INSIDE the measured window — a single
        # quick-shape burst finishes in ~10 ms, under one poll period
        t0 = time.perf_counter()
        total = 0
        while True:
            reqs = [eng.submit(p, max_new_tokens=new_tokens)
                    for p in prompts]
            for r in reqs:
                r.result(600)
            total += sum(len(r.tokens) for r in reqs)
            if time.perf_counter() - t0 >= min_wall:
                break
        wall = time.perf_counter() - t0
        return total / wall

    out = {}
    telemetry.enable()
    try:
        for tag in ('baseline', 'refresh'):
            telemetry.reset()
            dec.reset()
            eng = ServingEngine(dec)
            eng.start()
            sub, stop_bump, bump = None, None, None
            if tag == 'refresh':
                sub = ParamSubscriber(['127.0.0.1:%d' % srv.port], dec,
                                      engine=eng, poll_secs=0.02)
                sub.start()
                stop_bump = threading.Event()
                seq = [0]

                def bump_loop():
                    while not stop_bump.wait(0.03):
                        seq[0] += 1
                        svc.on_send_var('r@GRAD', 0, np.zeros(1, 'f4'),
                                        seq=('bench', seq[0]))
                        seq[0] += 1
                        svc.on_batch_barrier(0, seq=('bench', seq[0]))
                bump = threading.Thread(target=bump_loop, daemon=True)
                bump.start()
            try:
                tps = burst(eng)
            finally:
                if stop_bump is not None:
                    stop_bump.set()
                    bump.join(timeout=10)
                if sub is not None:
                    sub.stop()
                eng.stop()
            h = telemetry.snapshot()['hists'].get('serving.token_latency')
            p50 = telemetry.hist_quantile(h, 0.50) if h else None
            p99 = telemetry.hist_quantile(h, 0.99) if h else None
            out[tag] = {'tokens_per_sec': round(tps, 2),
                        'token_p50_ms':
                            round(p50 * 1e3, 3) if p50 else 0.0,
                        'token_p99_ms':
                            round(p99 * 1e3, 3) if p99 else 0.0,
                        'refreshes': sub.refreshes if sub else 0,
                        'refresh_failures': sub.failures if sub else 0}
    finally:
        telemetry.disable()
        telemetry.reset()
        tcli = PSClient('127.0.0.1:%d' % srv.port, trainer_id=0)
        tcli.complete()
        tcli.close()
        sthread.join(timeout=10)
    base_p99 = out['baseline']['token_p99_ms']
    ratio = (out['refresh']['token_p99_ms'] / base_p99
             if base_p99 else 0.0)
    return {'mode': 'refresh', 'slots': slots,
            'requests': n_requests,
            'baseline': out['baseline'], 'refresh': out['refresh'],
            'refresh_p99_ratio': round(ratio, 3)}


def _spec_leg(cfg, quick):
    """Speculative-decoding A/B leg at EQUAL cache HBM: plain paged
    greedy decode vs draft/verify speculation over the same page-pool
    machinery (serving/speculative.py), measuring steady-state decode
    tokens/s over full slot pools.

    The model is a deeper variant of the bench config whose tail
    blocks' residual contributions (attention proj + FFN down) are
    zeroed — a stand-in for a well-distilled draft: the
    FLAGS_spec_draft_layers-deep self-draft then AGREES with the
    target, so the leg exercises the high-accept regime the
    optimization targets while the accept rate stays MEASURED, not
    assumed (nothing in the harness forces acceptance — the verify
    pass scores every proposal). Equal HBM: the draft cache costs
    pages * draft_layers/target_layers extra, so the plain baseline's
    pool gets that many more pages instead."""
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.transpiler.decode_transpiler import \
        extract_decode_spec

    layers = 2 if quick else 4
    draft_layers = 1
    spec_k = 3 if quick else 4
    slots = 4 if quick else 8
    scfg = tfm.TransformerConfig(vocab=cfg.vocab, dim=cfg.dim,
                                 heads=cfg.heads, layers=layers,
                                 ffn=cfg.ffn, max_len=cfg.max_len,
                                 use_tp=False, use_sp=False)
    label = 'L%d_D%d_T%d' % (scfg.layers, scfg.dim, scfg.max_len)
    spred = _build_predictor(scfg)
    dspec = extract_decode_spec(spred._program)
    for blk in dspec.blocks[draft_layers:]:
        for w, b in (blk['proj'], blk['down']):
            for name in (w, b):
                if name is None:
                    continue
                old = np.asarray(spred._scope.find_var(name))
                spred._scope.set_var(name, np.zeros_like(old))

    pt = max(2, scfg.max_len // 8)
    pages_per_slot = -(-scfg.max_len // pt)
    spec_pages = slots * pages_per_slot + 1
    # plain baseline absorbs the draft pool's HBM as extra target pages
    plain_pages = (slots * pages_per_slot
                   + -(-slots * pages_per_slot * draft_layers // layers)
                   + 1)
    rng = np.random.RandomState(9)
    prompts = [list(rng.randint(1, scfg.vocab, 2)) for _ in range(slots)]
    iters = scfg.max_len - 4

    plain = spred.prepare_decoding(slots=slots,
                                   page_tokens=pt, kv_pages=plain_pages,
                                   prefill_chunk=scfg.max_len)
    ids = plain.prefill(prompts, list(range(slots)))
    toks = np.asarray(ids, np.int64)
    pos = np.array([len(p) for p in prompts], np.int32)
    plain.decode_step(toks, pos)        # compile outside the window
    plain.reset()
    ids = plain.prefill(prompts, list(range(slots)))
    toks = np.asarray(ids, np.int64)
    pos = np.array([len(p) for p in prompts], np.int32)
    total_p, t_p = 0, 0.0
    ref_streams = [[int(t)] for t in toks]
    for _ in range(iters):
        t0 = time.perf_counter()
        ids = plain.decode_step(toks, pos)
        t_p += time.perf_counter() - t0
        toks = np.asarray(ids, np.int64)
        pos += 1
        total_p += slots
        for s in range(slots):
            ref_streams[s].append(int(ids[s]))
    plain_tps = total_p / t_p

    sdec = spred.prepare_decoding(slots=slots, speculative=True,
                                  spec_k=spec_k,
                                  draft_layers=draft_layers,
                                  page_tokens=pt, kv_pages=spec_pages,
                                  prefill_chunk=scfg.max_len)
    ids = sdec.prefill(prompts, list(range(slots)))
    toks = np.asarray(ids, np.int64)
    pos = np.array([len(p) for p in prompts], np.int32)
    sdec.spec_step(toks, pos)           # compile outside the window
    sdec.reset()
    ids = sdec.prefill(prompts, list(range(slots)))
    toks = np.asarray(ids, np.int64)
    pos = np.array([len(p) for p in prompts], np.int32)
    total_s, t_s = 0, 0.0
    spec_streams = [[int(t)] for t in toks]
    while int(pos.max()) < scfg.max_len - 1:
        t0 = time.perf_counter()
        out = sdec.spec_step(toks, pos)
        t_s += time.perf_counter() - t0
        for s, emitted in out.items():
            toks[s] = emitted[-1]
            pos[s] += len(emitted)
            total_s += len(emitted)
            spec_streams[s].extend(int(t) for t in emitted)
    spec_tps = total_s / t_s
    # the acceptance rule's guarantee, checked in the harness itself:
    # speculation changed throughput, not one emitted token
    for s in range(slots):
        n = min(len(ref_streams[s]), len(spec_streams[s]))
        assert spec_streams[s][:n] == ref_streams[s][:n], \
            'speculative stream %d diverged from plain greedy' % s
    st = sdec.spec_stats()
    return {'mode': 'spec', 'config': label, 'slots': slots,
            'spec_k': spec_k, 'draft_layers': draft_layers,
            'target_layers': layers, 'page_tokens': pt,
            'plain_kv_pages': plain_pages, 'spec_kv_pages': spec_pages,
            'plain_paged_tokens_per_sec': round(plain_tps, 2),
            'spec_tokens_per_sec': round(spec_tps, 2),
            'spec_accept_rate': round(st['accept_rate'], 4),
            'spec_effective_tokens_per_step':
                round(st['effective_tokens_per_step'], 3),
            'spec_fallback_steps': st['fallback_steps'],
            'spec_speedup': round(spec_tps / plain_tps, 2)}


def _preempt_leg(pred, cfg, quick):
    """Preempt-first capacity leg: a mixed-tier overload burst (every
    3rd request priority 1) through a ServingEngine whose paged pool
    holds only ~half its lanes at full window — finishing the burst
    REQUIRES preempting low-tier streams (host-RAM swap, or drop +
    re-prefill when FLAGS_serving_swap_host_mb is dry) and resuming
    them bit-exactly. Two acceptance numbers: overload_completion_rate
    (completed / attempted, higher is better — preempt-first capacity
    means overload costs low-tier latency, not completions) and
    preempt_resume_p99_ms (p99 of serving.resume_latency: queue-front
    re-entry + page restore or re-prefill until the stream decodes
    again, lower is better)."""
    from paddle_tpu.obs import telemetry
    from paddle_tpu.serving import ServingEngine

    lanes = 4
    pt = max(2, cfg.max_len // 8)
    chunk = max(1, cfg.max_len // 4)
    new_tokens = 4 if quick else 8
    prompt_len = max(1, cfg.max_len // 2 - new_tokens)
    # the pool holds HALF the lanes at their full stream footprint
    # (prompt + budget): decode pressure must preempt, not queue
    pages_per_stream = -(-(prompt_len + new_tokens) // pt)
    num_pages = (lanes // 2) * pages_per_stream + 1
    n_requests = 24 if quick else 48
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, cfg.vocab, prompt_len)
               for _ in range(n_requests)]
    prios = [1 if i % 3 == 0 else 0 for i in range(n_requests)]

    dec = pred.prepare_decoding(slots=lanes, page_tokens=pt,
                                kv_pages=num_pages, prefill_chunk=chunk)
    dec.open_stream(0, list(prompts[0]))    # compile outside the window
    while dec.prefill_step(0) is None:
        pass
    warm_pos = np.zeros(lanes, 'int32')
    warm_pos[0] = prompt_len
    dec.decode_step(np.zeros(lanes, 'int64'), warm_pos)
    dec.reset()

    telemetry.enable()
    try:
        telemetry.reset()
        sheds = 0
        t0 = time.perf_counter()
        with ServingEngine(dec) as eng:
            reqs = []
            for p, prio in zip(prompts, prios):
                try:
                    reqs.append(eng.submit(p, max_new_tokens=new_tokens,
                                           priority=prio))
                except RuntimeError:    # queue full: tier-0 only
                    sheds += 1
            for r in reqs:
                r.result(600)
            stats = eng.stats()
        wall = time.perf_counter() - t0
        done = sum(1 for r in reqs if r.state == 'DONE')
        total = sum(len(r.tokens) for r in reqs)
        snap = telemetry.snapshot()
        h = snap['hists'].get('serving.resume_latency')
        p99 = telemetry.hist_quantile(h, 0.99) if h else None
        p50 = telemetry.hist_quantile(h, 0.50) if h else None
        ctrs = snap['counters']
    finally:
        telemetry.disable()
        telemetry.reset()
    return {'mode': 'preempt', 'lanes': lanes, 'page_tokens': pt,
            'kv_pages': num_pages, 'requests': n_requests,
            'high_tier_requests': sum(prios), 'queue_sheds': sheds,
            'preempt_tokens_per_sec': round(total / wall, 2),
            'overload_completion_rate':
                round(done / float(n_requests), 4),
            'preemptions': ctrs.get('serving.preemptions', 0),
            'swapped_pages': ctrs.get('serving.swapped_pages', 0),
            'swap_bytes': ctrs.get('serving.swap_bytes', 0),
            'resumes': h['count'] if h else 0,
            'preempted_streams_now': stats.get('preempted_streams', 0),
            'preempt_resume_p50_ms':
                round(p50 * 1e3, 3) if p50 else 0.0,
            'preempt_resume_p99_ms':
                round(p99 * 1e3, 3) if p99 else 0.0}


def _fleet_leg(cfg, quick, replicas=2):
    """Fleet serving leg: `replicas` serve_replica.py subprocesses
    behind an in-process FleetRouter, one concurrent burst through the
    whole fleet. fleet_tokens_per_sec is aggregate decode throughput
    across replicas; fleet_p99_ttft_ms prices dispatch + replica queue
    + prefill at burst concurrency (the admission-control SLO's raw
    signal). Both land in the acceptance summary for perf_gate.py."""
    import socket as _socket
    import subprocess

    import paddle_tpu as fluid
    from paddle_tpu.distributed import wire
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving import FleetRouter

    n_requests = 16 if quick else 64
    new_tokens = 4 if quick else 16
    slots = 4 if quick else 8
    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.RandomState(5)
    procs = []
    with tempfile.TemporaryDirectory() as tmp:
        # the replicas load from disk, so this leg persists its own
        # save_inference_model dir for their lifetime
        model_dir = os.path.join(tmp, 'model')
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            tokens = fluid.layers.data(
                'tokens', shape=[1, cfg.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = tfm.language_model_logits(tokens, cfg)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(model_dir, ['tokens'],
                                          [logits], exe,
                                          main_program=main_prog)
        eps = []
        for _ in range(replicas):
            s = _socket.socket()
            s.bind(('127.0.0.1', 0))
            eps.append('127.0.0.1:%d' % s.getsockname()[1])
            s.close()
        env = _replica_env()
        try:
            for ep in eps:
                procs.append(subprocess.Popen(
                    [sys.executable,
                     os.path.join(here, 'serve_replica.py')],
                    env=dict(env, SERVE_MODEL_DIR=model_dir,
                             SERVE_ENDPOINT=ep,
                             SERVE_SLOTS=str(slots)),
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            router = FleetRouter(eps, probe_secs=0.1).start()
            try:
                router.wait_healthy(timeout=300.0)
                prompts = [rng.randint(1, cfg.vocab,
                                       max(1, cfg.max_len // 2))
                           for _ in range(n_requests)]
                # warm every replica's jit cache outside the window:
                # least-loaded dispatch spreads one prompt per slot
                warm = [router.submit(prompts[0],
                                      max_new_tokens=new_tokens)
                        for _ in range(replicas * slots)]
                for r in warm:
                    r.wait(600.0)
                t0 = time.perf_counter()
                reqs = [router.submit(p, max_new_tokens=new_tokens)
                        for p in prompts]
                for r in reqs:
                    r.wait(600.0)
                wall = time.perf_counter() - t0
                total = sum(len(r.tokens) for r in reqs)
                ttfts = sorted(r.first_token_at - r.submitted_at
                               for r in reqs if r.first_token_at)
                p99 = ttfts[int(0.99 * (len(ttfts) - 1))]
                stats = router.stats()
            finally:
                router.stop()
            for ep in eps:
                host, port = ep.rsplit(':', 1)
                try:
                    with _socket.create_connection(
                            (host, int(port)), timeout=5.0) as s:
                        wire.write_msg(s, wire.COMPLETE, {'seq': 0})
                        wire.read_msg(s)
                except (ConnectionError, OSError):
                    pass
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    return {'mode': 'fleet', 'replicas': replicas, 'slots': slots,
            'requests': n_requests,
            'fleet_tokens_per_sec': round(total / wall, 2),
            'fleet_p99_ttft_ms': round(p99 * 1e3, 1),
            'failovers': stats['failovers'],
            'completed': stats['completed']}


def _warm_replica_direct(ep, prompt, budget, timeout=300.0):
    """Warm one replica's jit cache over a direct wire connection —
    SRV_SUBMIT then SRV_HEALTH until idle. Deliberately avoids
    SRV_POLL so a fault plan keyed on poll events (the --hedge leg's
    stalled replica) is not consumed by warmup."""
    import socket as _socket

    from paddle_tpu.distributed import wire

    host, port = ep.rsplit(':', 1)
    deadline = time.monotonic() + timeout
    while True:       # the replica binds only after its model loads
        try:
            s = _socket.create_connection((host, int(port)),
                                          timeout=5.0)
            break
        except OSError:
            if time.monotonic() >= deadline:
                raise
            time.sleep(0.25)
    with s:
        wire.write_msg(s, wire.SRV_SUBMIT,
                       {'seq': 0, 'rid': 'warm', 'mnt': int(budget)},
                       np.asarray(prompt, np.int64))
        wire.read_msg(s)
        seq = 1
        while True:
            wire.write_msg(s, wire.SRV_HEALTH, {'seq': seq})
            _, meta, _ = wire.read_msg(s)
            seq += 1
            if not meta.get('active') and not meta.get('queue_depth'):
                return
            if time.monotonic() > deadline:
                raise RuntimeError('warmup did not drain on %s' % ep)
            time.sleep(0.25)


def _hedge_leg(cfg, quick, replicas=2):
    """Gray-failure tail-tolerance leg: the fleet topology of
    _fleet_leg, but replica0 carries a FaultPlan that stalls its first
    several SRV_POLL replies for seconds each — alive-but-slow, health
    probes still green — while the router runs with hedged dispatch
    (FLAGS_fleet_hedge_ms) and the progress watchdog armed.

    degraded_p99_ttft_ms is the p99 time-to-first-token of a burst
    through that degraded fleet (lower is better: without hedging it
    would sit at the stall duration, with hedging the duplicate dispatch
    to the healthy replica answers in ~hedge_ms + prefill).
    hedge_win_rate is hedge_wins / hedges from router.stats() (higher
    is better — hedges that lose were wasted work). Both land in the
    acceptance summary for perf_gate.py."""
    import socket as _socket
    import subprocess

    import paddle_tpu as fluid
    from paddle_tpu.distributed import wire
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving import FleetRouter

    n_requests = 16 if quick else 48
    new_tokens = 4 if quick else 8
    slots = 4 if quick else 8
    stall_secs = 2.0
    n_stalls = 8
    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.RandomState(7)
    procs = []
    with tempfile.TemporaryDirectory() as tmp:
        model_dir = os.path.join(tmp, 'model')
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            tokens = fluid.layers.data(
                'tokens', shape=[1, cfg.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = tfm.language_model_logits(tokens, cfg)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(model_dir, ['tokens'],
                                          [logits], exe,
                                          main_program=main_prog)
        eps = []
        for _ in range(replicas):
            s = _socket.socket()
            s.bind(('127.0.0.1', 0))
            eps.append('127.0.0.1:%d' % s.getsockname()[1])
            s.close()
        env = _replica_env()
        # replica0: stall each of the first n_stalls SRV_POLL replies
        # for stall_secs — the gray window the hedges must cover
        plan = json.dumps({'rules': [
            {'when': 'recv', 'type': 'SRV_POLL', 'nth': n,
             'action': 'stall', 'secs': stall_secs}
            for n in range(1, n_stalls + 1)]})
        try:
            for i, ep in enumerate(eps):
                rep_env = dict(env, SERVE_MODEL_DIR=model_dir,
                               SERVE_ENDPOINT=ep,
                               SERVE_SLOTS=str(slots))
                if i == 0:
                    rep_env['FLAGS_fault_plan'] = plan
                procs.append(subprocess.Popen(
                    [sys.executable,
                     os.path.join(here, 'serve_replica.py')],
                    env=rep_env,
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            # warm over direct connections (no SRV_POLL, so the stall
            # budget survives into the measured window), THEN arm the
            # gray-failure machinery and construct the router
            prompts = [rng.randint(1, cfg.vocab,
                                   max(1, cfg.max_len // 2))
                       for _ in range(n_requests)]
            for ep in eps:
                _warm_replica_direct(ep, prompts[0], new_tokens)
            from paddle_tpu import flags
            saved = {k: flags.get_flag(k)
                     for k in ('fleet_hedge_ms',
                               'fleet_progress_timeout_secs')}
            flags.set_flags({'FLAGS_fleet_hedge_ms': 150.0,
                             'FLAGS_fleet_progress_timeout_secs': 1.0})
            try:
                router = FleetRouter(eps, probe_secs=0.1).start()
            finally:
                flags.set_flags(
                    {'FLAGS_' + k: v for k, v in saved.items()})
            try:
                router.wait_healthy(timeout=300.0)
                t0 = time.perf_counter()
                reqs = [router.submit(p, max_new_tokens=new_tokens)
                        for p in prompts]
                for r in reqs:
                    r.wait(600.0)
                wall = time.perf_counter() - t0
                total = sum(len(r.tokens) for r in reqs)
                ttfts = sorted(r.first_token_at - r.submitted_at
                               for r in reqs if r.first_token_at)
                p99 = ttfts[int(0.99 * (len(ttfts) - 1))]
                stats = router.stats()
            finally:
                router.stop()
            for ep in eps:
                host, port = ep.rsplit(':', 1)
                try:
                    with _socket.create_connection(
                            (host, int(port)), timeout=5.0) as s:
                        wire.write_msg(s, wire.COMPLETE, {'seq': 0})
                        wire.read_msg(s)
                except (ConnectionError, OSError):
                    pass
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
    return {'mode': 'hedge', 'replicas': replicas, 'slots': slots,
            'requests': n_requests, 'stall_secs': stall_secs,
            'degraded_tokens_per_sec': round(total / wall, 2),
            'degraded_p99_ttft_ms': round(p99 * 1e3, 1),
            'hedges': stats['hedges'],
            'hedge_wins': stats['hedge_wins'],
            'hedge_win_rate': round(
                stats['hedge_wins'] / max(1, stats['hedges']), 4),
            'gray_marks': stats['gray_marks'],
            'failovers': stats['failovers'],
            'completed': stats['completed']}


def _disagg_leg(cfg, quick, replicas=2):
    """Disaggregated prefill/decode A/B leg: the same shared-prefix
    burst through two fleets over identical paged replicas — once
    colocated (each decode replica prefills for itself) and once with
    a prefill-tier replica shipping KV pages over SRV_PAGE_FETCH
    (serving/disagg.py). Every request extends one page-aligned
    system prefix, so the disagg fleet prefills that prefix ONCE
    fleet-wide and the decode replicas adopt the shipped pages;
    disagg_p99_ttft_ms vs colocated_p99_ttft_ms prices what the ship
    path buys at burst concurrency, and fleet_prefix_hit_rate
    (decode-tier prefix-cache hits / lookups, via the fleet prefix
    directory's SRV_HEALTH feed) shows the sharing actually landing.
    Both go in the acceptance summary for perf_gate.py."""
    import socket as _socket
    import subprocess

    import paddle_tpu as fluid
    from paddle_tpu.distributed import wire
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving import FleetRouter

    n_requests = 16 if quick else 48
    new_tokens = 4 if quick else 8
    slots = 4
    pt = max(2, cfg.max_len // 8)
    kv_pages = 64 if quick else 256
    here = os.path.dirname(os.path.abspath(__file__))
    rng = np.random.RandomState(11)
    # a page-aligned shared system prefix (4 full pages) + a 2-token
    # per-request tail: the whole burst shares one shippable chain
    sys_prefix = [int(t) for t in rng.randint(1, cfg.vocab, 4 * pt)]
    prompts = [sys_prefix +
               [int(t) for t in rng.randint(1, cfg.vocab, 2)]
               for _ in range(n_requests)]

    def one_fleet(model_dir, with_prefill):
        eps = []
        for _ in range(replicas + (1 if with_prefill else 0)):
            s = _socket.socket()
            s.bind(('127.0.0.1', 0))
            eps.append('127.0.0.1:%d' % s.getsockname()[1])
            s.close()
        decode_eps, prefill_eps = eps[:replicas], eps[replicas:]
        env = _replica_env()
        procs = []
        try:
            for ep in eps:
                procs.append(subprocess.Popen(
                    [sys.executable,
                     os.path.join(here, 'serve_replica.py')],
                    env=dict(env, SERVE_MODEL_DIR=model_dir,
                             SERVE_ENDPOINT=ep,
                             SERVE_SLOTS=str(slots),
                             SERVE_WORKERS='1',
                             SERVE_PAGE_TOKENS=str(pt),
                             SERVE_KV_PAGES=str(kv_pages),
                             SERVE_PREFILL_CHUNK=str(cfg.max_len)),
                    stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL))
            # warm jit caches with a prompt OUTSIDE the shared prefix
            # so the measured burst starts prefix-cold everywhere
            for ep in eps:
                _warm_replica_direct(ep, [1, 2, 3], 2)
            router = FleetRouter(decode_eps,
                                 prefill_replicas=prefill_eps,
                                 probe_secs=0.1).start()
            try:
                router.wait_healthy(timeout=300.0)
                t0 = time.perf_counter()
                reqs = [router.submit(p, max_new_tokens=new_tokens)
                        for p in prompts]
                for r in reqs:
                    r.wait(600.0)
                wall = time.perf_counter() - t0
                total = sum(len(r.tokens) for r in reqs)
                ttfts = sorted(r.first_token_at - r.submitted_at
                               for r in reqs if r.first_token_at)
                p99 = ttfts[int(0.99 * (len(ttfts) - 1))]
                # one probe period so the replicas' ship / prefix
                # counters (SRV_HEALTH truth) land in router.stats()
                time.sleep(0.3)
                stats = router.stats()
            finally:
                router.stop()
            for ep in eps:
                host, port = ep.rsplit(':', 1)
                try:
                    with _socket.create_connection(
                            (host, int(port)), timeout=5.0) as s:
                        wire.write_msg(s, wire.COMPLETE, {'seq': 0})
                        wire.read_msg(s)
                except (ConnectionError, OSError):
                    pass
            for p in procs:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        return {'p99': p99, 'tps': total / wall, 'stats': stats}

    with tempfile.TemporaryDirectory() as tmp:
        model_dir = os.path.join(tmp, 'model')
        main_prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main_prog, startup):
            tokens = fluid.layers.data(
                'tokens', shape=[1, cfg.max_len, 1], dtype='int64',
                append_batch_size=False)
            logits = tfm.language_model_logits(tokens, cfg)
        exe = fluid.Executor(fluid.TPUPlace())
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            fluid.io.save_inference_model(model_dir, ['tokens'],
                                          [logits], exe,
                                          main_program=main_prog)
        colo = one_fleet(model_dir, with_prefill=False)
        dis = one_fleet(model_dir, with_prefill=True)
    return {'mode': 'disagg', 'replicas': replicas, 'slots': slots,
            'page_tokens': pt, 'kv_pages': kv_pages,
            'requests': n_requests, 'prefix_tokens': len(sys_prefix),
            'colocated_p99_ttft_ms': round(colo['p99'] * 1e3, 1),
            'disagg_p99_ttft_ms': round(dis['p99'] * 1e3, 1),
            'colocated_tokens_per_sec': round(colo['tps'], 2),
            'disagg_tokens_per_sec': round(dis['tps'], 2),
            'fleet_prefix_hit_rate':
                round(dis['stats']['prefix_hit_rate'], 4),
            'colocated_prefix_hit_rate':
                round(colo['stats']['prefix_hit_rate'], 4),
            'pages_shipped': dis['stats']['pages_shipped'],
            'ship_bytes': dis['stats']['ship_bytes'],
            'pages_deduped': dis['stats']['pages_deduped'],
            'local_reprefills': dis['stats']['local_reprefills'],
            'prefix_dir_entries': dis['stats']['prefix_dir_entries']}


def _hbm_per_chip_mb(dec):
    """Max bytes any one chip holds of this predictor's weights + KV
    state (the serve-footprint-per-chip number the mesh leg compares).
    Sharded jax arrays are charged per shard to the device that holds
    it; host numpy state charges to chip 0 (the single-chip path)."""
    per = {}
    names = (set(dec._pair.spec.param_names())
             | set(dec._pair.cache_names))
    seen = set()
    for name in names:
        arr = dec._scope.find_var(name)
        if arr is None or id(arr) in seen:
            continue
        seen.add(id(arr))
        shards = getattr(arr, 'addressable_shards', None)
        if shards is not None:
            for sh in shards:
                key = sh.device.id
                per[key] = per.get(key, 0) + int(sh.data.nbytes)
        else:
            per[0] = per.get(0, 0) + int(getattr(arr, 'nbytes', 0))
    return round(max(per.values()) / 1e6, 3) if per else 0.0


def _mesh_leg(cfg, quick, iters, mesh_shape):
    """Mesh-sharded serving A/B leg (serving/mesh.py): the same paged
    decode pool single-chip vs GSPMD over `mesh_shape`, same weights.
    mesh_tokens_per_sec is steady-state full-pool decode throughput of
    the SPMD program (one compiled step across the mesh, device-side
    argmax — only token ids leave); mesh_tokens_per_sec_per_chip
    divides by the mesh size (the number that must not crater — a mesh
    that serves N× the chips for the same aggregate is a regression).
    single_hbm_per_chip_mb vs mesh_hbm_per_chip_mb shows the heads-
    sharded page pool + column-sharded weights actually splitting
    across chips. The leg asserts the mesh stream is BIT-EXACT vs the
    single-chip stream before timing anything."""
    slots = 4 if quick else 8
    pt = max(2, cfg.max_len // 8)
    chunk = max(1, cfg.max_len // 2)
    steps = max(4, cfg.max_len - 4)
    rng = np.random.RandomState(17)
    prompts = [list(rng.randint(1, cfg.vocab, 2)) for _ in range(slots)]
    probe = list(rng.randint(1, cfg.vocab, 3))
    n_probe = min(8, cfg.max_len - len(probe) - 1)

    # ONE predictor for both runs: the A/B (and the bit-exact check)
    # is meaningful only over identical weights. Single-chip runs
    # first; the mesh run then reshards the shared parent scope.
    pred = _build_predictor(cfg)

    def run(mesh):
        dec = pred.prepare_decoding(slots=slots,
                                    page_tokens=pt,
                                    prefill_chunk=chunk, mesh=mesh)
        stream = dec.generate(probe, n_probe)
        dec.reset()
        ids = dec.prefill(prompts, list(range(slots)))
        toks = np.asarray(ids, np.int64)
        pos = np.array([len(p) for p in prompts], np.int32)
        dec.decode_step(toks, pos)          # compile outside the window
        dec.reset()
        ids = dec.prefill(prompts, list(range(slots)))
        toks = np.asarray(ids, np.int64)
        pos = np.array([len(p) for p in prompts], np.int32)
        t0 = time.perf_counter()
        for _ in range(steps):
            toks = np.asarray(dec.decode_step(toks, pos), np.int64)
            pos += 1
        dt = time.perf_counter() - t0
        jit = dec.jit_cache_stats()
        return {'tps': slots * steps / dt, 'stream': stream,
                'hbm_mb': _hbm_per_chip_mb(dec),
                'devices': dec.mesh_devices, 'jit': jit}

    single = run('')
    mesh = run(mesh_shape)
    assert mesh['stream'] == single['stream'], \
        'mesh greedy stream diverged from single-chip'
    return {'mode': 'mesh', 'mesh_shape': mesh_shape,
            'mesh_devices': mesh['devices'], 'slots': slots,
            'page_tokens': pt, 'decode_steps': steps,
            'bit_exact': True,
            'single_tokens_per_sec': round(single['tps'], 2),
            'mesh_tokens_per_sec': round(mesh['tps'], 2),
            'mesh_tokens_per_sec_per_chip':
                round(mesh['tps'] / max(1, mesh['devices']), 2),
            'single_hbm_per_chip_mb': single['hbm_mb'],
            'mesh_hbm_per_chip_mb': mesh['hbm_mb'],
            'mesh_compiled_segments': mesh['jit']['compiled_segments']}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--quick', action='store_true',
                    help='one tiny shape, bs 1 + 4 (CI smoke)')
    ap.add_argument('--full', action='store_true',
                    help='L4/D1024/T512 benchmark shape; needs a TPU and '
                         'fails without one; refuses --fleet/--hedge/'
                         '--disagg (this process holds the chip)')
    ap.add_argument('--refresh', action='store_true',
                    help='add the online-refresh cost leg: the engine '
                         'burst with vs without a concurrent '
                         'ParamSubscriber install loop '
                         '(refresh_p99_ratio in the summary)')
    ap.add_argument('--fleet', action='store_true',
                    help='add the fleet serving leg: a FleetRouter '
                         'over 2 replica subprocesses under burst '
                         'load (fleet_tokens_per_sec + '
                         'fleet_p99_ttft_ms in the summary)')
    ap.add_argument('--hedge', action='store_true',
                    help='add the gray-failure tail-tolerance leg: the '
                         'fleet topology with one deliberately stalled '
                         'replica, hedged dispatch + progress watchdog '
                         'armed (degraded_p99_ttft_ms + hedge_win_rate '
                         'in the summary)')
    ap.add_argument('--disagg', action='store_true',
                    help='add the disaggregated prefill/decode A/B '
                         'leg: a shared-prefix burst through a '
                         'colocated fleet vs the same replicas behind '
                         'a KV-page-shipping prefill tier '
                         '(disagg_p99_ttft_ms + fleet_prefix_hit_rate '
                         'in the summary)')
    ap.add_argument('--preempt', action='store_true',
                    help='add the preempt-first capacity leg: a '
                         'mixed-tier overload burst against a paged '
                         'pool half the burst size, forcing SLO-tiered '
                         'preemption + bit-exact resume '
                         '(overload_completion_rate + '
                         'preempt_resume_p99_ms in the summary)')
    ap.add_argument('--spec', action='store_true',
                    help='add the speculative-decoding A/B leg: '
                         'draft/verify speculation vs plain paged '
                         'greedy decode at equal cache HBM '
                         '(spec_tokens_per_sec, spec_accept_rate, '
                         'spec_speedup in the summary)')
    ap.add_argument('--mesh', action='store_true',
                    help='add the mesh-sharded serving A/B leg: the '
                         'same paged decode single-chip vs one GSPMD '
                         'SPMD program over --mesh-shape, bit-exact '
                         'checked (mesh_tokens_per_sec + per-chip '
                         'HBM in the summary)')
    ap.add_argument('--mesh-shape', default='tp=2',
                    help="mesh axis spec for --mesh (default 'tp=2')")
    ap.add_argument('--iters', type=int, default=20)
    args = ap.parse_args()
    if args.full:
        legs = [leg for leg in ('fleet', 'hedge', 'disagg')
                if getattr(args, leg)]
        if legs:
            ap.error('--full builds its predictor on the chip in this '
                     'process, and a chip belongs to one process: the '
                     'replica children of --%s could not hold it. Run '
                     'those legs without --full (CPU counts).'
                     % ' --'.join(legs))
    else:
        os.environ.setdefault('JAX_PLATFORMS', 'cpu')
        if args.mesh:
            # must land before jax initializes its backend: the CPU
            # mesh leg needs more than one (virtual) device
            os.environ.setdefault(
                'XLA_FLAGS', '--xla_force_host_platform_device_count=8')

    from paddle_tpu.models import transformer as tfm
    if args.full:
        from paddle_tpu.obs import perf
        print(json.dumps(dict(perf.require_tpu(), mode='device')),
              flush=True)
        # 16 heads of d=64 miss the flash kernel's d % 128 tiling: this
        # shape runs the naive contraction (pallas.flash.naive counts it)
        cfg = tfm.TransformerConfig(vocab=32768, dim=1024, heads=16,
                                    layers=4, ffn=4096, max_len=512,
                                    use_tp=False, use_sp=False,
                                    flash_attention=True)
        batch_sizes = [1, 4, 16, 64]
    elif args.quick:
        cfg = tfm.TransformerConfig(vocab=128, dim=32, heads=2,
                                    layers=1, ffn=64, max_len=16,
                                    use_tp=False, use_sp=False)
        batch_sizes = [1, 4]
    else:
        cfg = tfm.TransformerConfig(vocab=512, dim=128, heads=4,
                                    layers=2, ffn=256, max_len=128,
                                    use_tp=False, use_sp=False)
        batch_sizes = [1, 4, 16, 64]

    label = 'L%d_D%d_T%d' % (cfg.layers, cfg.dim, cfg.max_len)
    pred = _build_predictor(cfg)

    rec_tps, rec_dt = _recompute_tokens_per_sec(pred, cfg, args.iters)
    print(json.dumps({'mode': 'recompute', 'config': label,
                      'infer_decode_recompute_tokens_per_sec':
                          round(rec_tps, 2),
                      'step_ms': round(rec_dt * 1e3, 2)}), flush=True)

    best = None
    for bs in batch_sizes:
        tps, step_ms, prefill_ms = _cached_tokens_per_sec(
            pred, cfg, bs, args.iters)
        row = {'mode': 'cached', 'config': label, 'slots': bs,
               'infer_decode_cached_tokens_per_sec': round(tps, 2),
               'step_ms': round(step_ms, 2),
               'infer_decode_prefill_ms': round(prefill_ms, 1)}
        print(json.dumps(row), flush=True)
        if best is None or tps > best['tps']:
            best = {'bs': bs, 'tps': tps}

    eng_row = _engine_leg(pred, cfg, slots=batch_sizes[-1],
                          n_requests=2 * batch_sizes[-1],
                          new_tokens=4 if args.quick else 16)
    eng_row.update({'mode': 'engine', 'config': label})
    print(json.dumps(eng_row), flush=True)

    summary = {'summary': 'acceptance', 'infer_decode_config': label,
               'infer_decode_recompute_tokens_per_sec':
                   round(rec_tps, 2),
               'infer_decode_cached_tokens_per_sec':
                   round(best['tps'], 2), 'best_slots': best['bs'],
               'infer_decode_speedup': round(best['tps'] / rec_tps, 2)}

    if args.refresh:
        ref_row = _refresh_leg(pred, cfg, slots=batch_sizes[-1],
                               n_requests=2 * batch_sizes[-1],
                               new_tokens=4 if args.quick else 16)
        ref_row['config'] = label
        print(json.dumps(ref_row), flush=True)
        summary['refresh_p99_ratio'] = ref_row['refresh_p99_ratio']
        summary['refresh_installs'] = ref_row['refresh']['refreshes']

    if args.fleet:
        fleet_row = _fleet_leg(cfg, args.quick)
        fleet_row['config'] = label
        print(json.dumps(fleet_row), flush=True)
        summary['fleet_tokens_per_sec'] = \
            fleet_row['fleet_tokens_per_sec']
        summary['fleet_p99_ttft_ms'] = fleet_row['fleet_p99_ttft_ms']

    if args.hedge:
        hedge_row = _hedge_leg(cfg, args.quick)
        hedge_row['config'] = label
        print(json.dumps(hedge_row), flush=True)
        summary['degraded_p99_ttft_ms'] = \
            hedge_row['degraded_p99_ttft_ms']
        summary['hedge_win_rate'] = hedge_row['hedge_win_rate']

    if args.disagg:
        dis_row = _disagg_leg(cfg, args.quick)
        dis_row['config'] = label
        print(json.dumps(dis_row), flush=True)
        for key in ('disagg_p99_ttft_ms', 'colocated_p99_ttft_ms',
                    'fleet_prefix_hit_rate', 'pages_shipped',
                    'ship_bytes'):
            summary[key] = dis_row[key]

    if args.preempt:
        pre_row = _preempt_leg(pred, cfg, args.quick)
        pre_row['config'] = label
        print(json.dumps(pre_row), flush=True)
        for key in ('overload_completion_rate', 'preempt_resume_p99_ms',
                    'preemptions', 'preempt_tokens_per_sec'):
            summary[key] = pre_row[key]

    if args.spec:
        spec_row = _spec_leg(cfg, args.quick)
        print(json.dumps(spec_row), flush=True)
        for key in ('spec_tokens_per_sec', 'plain_paged_tokens_per_sec',
                    'spec_accept_rate', 'spec_speedup'):
            summary[key] = spec_row[key]

    if args.mesh:
        mesh_row = _mesh_leg(cfg, args.quick, args.iters,
                             args.mesh_shape)
        mesh_row['config'] = label
        print(json.dumps(mesh_row), flush=True)
        for key in ('mesh_tokens_per_sec', 'mesh_tokens_per_sec_per_chip',
                    'single_tokens_per_sec', 'mesh_hbm_per_chip_mb',
                    'single_hbm_per_chip_mb'):
            summary[key] = mesh_row[key]
        summary['mesh_shape'] = mesh_row['mesh_shape']

    print(json.dumps(summary), flush=True)
    return summary


if __name__ == '__main__':
    main()
