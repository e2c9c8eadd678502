"""chip_smoke.py: the quickest proof that the system still starts on the chip.

Drives the repo's two main paths once, through the entry points a user
calls, at the full width of the dense transformer of bench.py
(L12 / D2048 / 16 heads of d=128 / F8192 / T512 / V32768, batch 8) with
seeded random weights, in one process (a chip belongs to one process):

  device   JAX's default backend is a TPU whose device_kind is in the
           peak table (obs/perf.py), or the run stops here.
  train    Program IR -> backward + Momentum -> bf16 AMP ->
           Executor(startup) -> ParallelExecutor fed by py_reader, as
           bench._bench_lm does. Eight steps on one repeated seeded
           batch: loss finite and falling, one compile, the Mosaic
           flash-attention forward AND backward in the compiled step
           (and pallas.flash.naive == 0), a real peak_bytes_in_use, a
           non-empty compile cache.
  serve    save_inference_model -> AnalysisPredictor ->
           prepare_decoding() -> ServingEngine, as
           tools/serve_bench.py does: eight requests of mixed prompt
           lengths, two of them identical, complete with identical
           streams for the identical pair, prefill and decode each
           compile once, and the first served token is a near-argmax of
           the plain predictor's full-forward logits (a tolerance, not
           bit-exactness: fp32 matmuls run at reduced precision on a
           TPU).

  python chip_smoke.py             one chip: device, train, serve
  python chip_smoke.py --chips 4   four chips: device, train on one chip
                                   (the control), train at dp=4 on the
                                   same seed and global batch
  python chip_smoke.py --dry-run   CPU walk-through of the same code at a
                                   tiny width, Pallas in interpret mode;
                                   prints "platform": "cpu", "dry_run":
                                   true. Proves control flow, not the chip.

Every phase prints one JSON line; a phase that fails raises, so the run
exits non-zero and the last line is never printed. Step times are
printed as information: this script measures nothing.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import os
import re
import sys
import tempfile
import time

_T0 = time.perf_counter()
SEED = 21
BATCH = 8           # global batch, one chip or four
TRAIN_STEPS = 8     # one compiling step + seven more
SLOTS = 8


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


def _versions():
    out = {}
    for pkg in ('jax', 'jaxlib', 'libtpu'):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = 'not installed'
    return out


def _memory(stats):
    """The allocator's own numbers (None on the CPU). The peaks are
    cumulative over the process, so a later phase repeats an earlier
    phase's peak unless it exceeds it."""
    if stats is None:
        return None
    return {k: stats[k] for k in ('bytes_in_use', 'peak_bytes_in_use',
                                  'peak_bytes_reserved', 'bytes_limit')
            if k in stats}


def _flash_route(since=None):
    """The flash_attention() route counters (bumped at trace time),
    as a delta when `since` is an earlier reading. .get: they register
    when the flash module is first imported."""
    from paddle_tpu.obs import telemetry
    counters = telemetry.snapshot()['counters']
    return {k: counters.get(k, 0) - (since[k] if since else 0)
            for k in ('pallas.flash.kernel', 'pallas.flash.naive')}


def _emit(phase, env, **fields):
    row = {'phase': phase}
    row.update(env)
    row.update(fields)
    print(json.dumps(row), flush=True)


def _config(dry_run):
    from paddle_tpu.models import transformer as tfm
    if dry_run:
        # one head of d=128 and T=128: the smallest shape the flash
        # kernel tiles, so the dry run walks the kernel route too
        return tfm.TransformerConfig(vocab=512, dim=128, heads=1, layers=1,
                                     ffn=256, max_len=128, use_tp=False,
                                     use_sp=False, flash_attention=True)
    return tfm.TransformerConfig(vocab=32768, dim=2048, heads=16, layers=12,
                                 ffn=8192, max_len=512, use_tp=False,
                                 use_sp=False, flash_attention=True)


def phase_device(args):
    import paddle_tpu  # noqa: F401  (places the compile cache at import)
    import jax
    from paddle_tpu.obs import perf, telemetry
    telemetry.enable()
    if args.dry_run:
        dev = perf.describe_device()
        _check(dev['platform'] == 'cpu' and dev['n_devices'] >= args.chips,
               '--dry-run is the CPU walk-through; JAX reports %r' % dev)
    else:
        dev = perf.require_tpu(min_devices=args.chips)
    env = dict(dev, compile_cache_dir=jax.config.jax_compilation_cache_dir,
               **_versions())
    if args.dry_run:
        env['dry_run'] = True
    _emit('device', env, devices=[str(d) for d in jax.devices()])
    return env


def _flash_calls(hlo_texts):
    """(forward, backward) Mosaic flash custom calls in the compiled
    step, plus the leading (batch*heads) dim each one was compiled for.
    _fwd/_bwd are the jitted wrappers in pallas/flash_attention.py; jit
    names ride the op_name metadata of everything traced inside them."""
    fwd, bwd, bh = 0, 0, set()
    for text in hlo_texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            is_fwd, is_bwd = '_fwd' in line, '_bwd' in line
            fwd += is_fwd
            bwd += is_bwd
            m = re.search(r'= \(?\w+\[(\d+),', line)
            if m and (is_fwd or is_bwd):
                bh.add(int(m.group(1)))
    return fwd, bwd, sorted(bh)


def _custom_call_sample(hlo_texts, limit=4):
    lines = [re.sub(r'backend_config="[^"]*"', 'backend_config=...', ln)[:600]
             for t in hlo_texts for ln in t.splitlines()
             if 'custom-call' in ln]
    return lines[:limit]


def run_train(cfg, env, devices, label):
    """The bench._bench_lm training step on `devices`; returns the
    phase's row after asserting everything the docstring lists."""
    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.framework import Parameter
    from paddle_tpu.models import transformer as tfm

    t_phase = time.perf_counter()
    n = len(devices)
    dry_run = bool(env.get('dry_run'))
    # bf16 parameter gradients under AMP, as bench.main sets on the chip
    fluid.flags.set_flags({'FLAGS_amp_bf16_param_grads': True})
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        rdr = fluid.layers.py_reader(
            capacity=4,
            shapes=[(-1, cfg.max_len, 1), (-1, cfg.max_len, 1)],
            dtypes=['int64', 'int64'], name='smoke_reader_%s' % label,
            use_double_buffer=True)
        tokens, labels = fluid.layers.read_file(rdr)
        trunk = tfm.language_model_trunk(tokens, cfg)
        cost = fluid.layers.fused_softmax_cross_entropy(
            trunk, labels, cfg.vocab, chunk=min(4096, BATCH * cfg.max_len),
            name='lm_head')
        avg_cost = fluid.layers.mean(cost)
        opt = fluid.optimizer.Momentum(learning_rate=0.001, momentum=0.9)
        opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_cost)

    rng = np.random.RandomState(SEED)
    toks = rng.randint(0, cfg.vocab,
                       size=(BATCH, cfg.max_len, 1)).astype('int64')
    batch = [toks, np.roll(toks, -1, axis=1)]

    def provider():
        while True:
            yield batch

    before = _flash_route()
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace())
        exe.run(startup)
        pe = fluid.ParallelExecutor(use_cuda=True, loss_name=avg_cost.name,
                                    main_program=main_prog, devices=devices)
        rdr.decorate_tensor_provider(provider)
        rdr.start()
        losses, step_s = [], []
        try:
            for i in range(TRAIN_STEPS):
                t0 = time.perf_counter()
                out = pe.run(fetch_list=[avg_cost.name], return_numpy=False)
                jax.block_until_ready(out)
                step_s.append(time.perf_counter() - t0)
                losses.append(float(np.asarray(out[0])))
                if i == 0:
                    first_step_done = time.perf_counter()
                    compiled_first = pe.jit_cache_stats()['compiled_segments']
        finally:
            rdr.reset()
        compiled_last = pe.jit_cache_stats()['compiled_segments']
        hlo = pe.compiled_hlo_texts()
        placed = pe._put_feed(tokens.name, toks)
        shard_rows = sorted((str(s.device), s.data.shape[0])
                            for s in placed.addressable_shards)
        param_devices = {
            frozenset(scope.find_var(name).sharding.device_set)
            for name, var in main_prog.global_block().vars.items()
            if isinstance(var, Parameter)}
    route = _flash_route(before)

    _check(all(np.isfinite(losses)), 'non-finite loss: %r' % losses)
    _check(losses[-1] < losses[0], 'loss did not fall: %r' % losses)
    _check(compiled_last == compiled_first,
           'the step recompiled after its first run: %d -> %d segments'
           % (compiled_first, compiled_last))
    _check(hlo, 'no compiled HLO to check')
    _check(route['pallas.flash.naive'] == 0
           and route['pallas.flash.kernel'] >= cfg.layers,
           'flash_attention missed the kernel route: %r' % route)
    fwd, bwd, bh = _flash_calls(hlo)
    if not dry_run:
        _check(fwd >= cfg.layers and bwd >= cfg.layers,
               'Mosaic flash custom calls in the compiled step: %d forward, '
               '%d backward, want >= %d of each; custom calls seen: %r'
               % (fwd, bwd, cfg.layers, _custom_call_sample(hlo)))
        # the flash_attention op runs per shard under a mesh (JAX refuses
        # a bare Mosaic call there): each chip attends to its own rows
        _check(bh == [BATCH * cfg.heads // n],
               'flash kernels compiled for batch*heads %r per device, want '
               '%d' % (bh, BATCH * cfg.heads // n))
    _check(shard_rows == sorted((str(d), BATCH // n) for d in devices),
           'feed placement %r, want %d rows on each of %r'
           % (shard_rows, BATCH // n, devices))
    _check(param_devices == {frozenset(devices)},
           'parameters do not live on exactly %r' % (devices,))
    # XLA may split a large all-reduce into reduce-scatter + all-gather
    collectives = {
        op: sum(len(re.findall(r' %s(?:-start)?\(' % op, t)) for t in hlo)
        for op in ('all-reduce', 'reduce-scatter', 'all-gather')}
    if n > 1:
        _check(collectives['all-reduce'] + collectives['reduce-scatter'] > 0,
               'no gradient all-reduce in the dp step: %r' % collectives)

    cache_dir = env['compile_cache_dir']
    cache_entries = len(os.listdir(cache_dir)) \
        if os.path.isdir(cache_dir) else 0
    stats = devices[0].memory_stats()
    if not dry_run:
        # (the dry run's tiny step compiles under the cache's 1 s floor,
        # and the CPU allocator reports no stats)
        _check(cache_entries > 0, 'compile cache %r is empty after the '
               'train phase' % cache_dir)
        _check(stats and stats.get('peak_bytes_in_use', 0) > 0,
               'device.memory_stats() gave no peak_bytes_in_use: %r' % stats)
    row = {
        'config': 'L%d_D%d_H%d_F%d_T%d_V%d_bs%d_bf16' % (
            cfg.layers, cfg.dim, cfg.heads, cfg.ffn, cfg.max_len,
            cfg.vocab, BATCH),
        'dp': n, 'steps': TRAIN_STEPS,
        'losses': [round(x, 5) for x in losses],
        'compiled_segments': compiled_last,
        'flash_route': route,
        'mosaic_flash_fwd': fwd, 'mosaic_flash_bwd': bwd,
        'flash_bh_per_device': bh, 'flash_bh_global': BATCH * cfg.heads,
        'collectives': collectives,
        'feed_rows_per_device': BATCH // n,
        'memory_stats': _memory(stats),
        'compile_cache_entries': cache_entries,
        # the compiling step alone; from this phase's start (program
        # build, startup, compile); from the start of the process
        'first_step_s': round(step_s[0], 2),
        'phase_to_first_step_s': round(first_step_done - t_phase, 2),
        'process_to_first_step_s': round(first_step_done - _T0, 2),
        'info_step_ms': [round(s * 1e3, 1) for s in step_s[1:]],
    }
    _emit('train', env, **row)
    return row


def _release(device, limit_bytes=256 << 20):
    """Training state must be gone before the next phase asks for the
    same HBM: drop what Python holds and check the allocator agrees."""
    gc.collect()
    stats = device.memory_stats()
    if stats is not None:
        _check(stats['bytes_in_use'] < limit_bytes,
               'device still holds %d bytes after the phase was released'
               % stats['bytes_in_use'])


def phase_serve(cfg, env):
    import jax
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
    from paddle_tpu.models import transformer as tfm
    from paddle_tpu.serving import ServingEngine

    before = _flash_route()
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main_prog, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = tfm.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.TPUPlace())
    with tempfile.TemporaryDirectory() as tmp:
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(tmp, ['tokens'], [logits], exe,
                                          main_program=main_prog)
        pred = AnalysisPredictor(AnalysisConfig(tmp))
    dec = pred.prepare_decoding(slots=SLOTS)

    # prompt lengths 32..448 and 32 new tokens at T=512, scaled with T
    unit = cfg.max_len // 16
    rng = np.random.RandomState(SEED)
    prompts = [rng.randint(1, cfg.vocab, k * unit)
               for k in (1, 2, 4, 6, 8, 10, 14)]
    prompts.append(prompts[2].copy())           # two identical prompts
    t0 = time.perf_counter()
    with ServingEngine(dec) as eng:
        reqs = [eng.submit(p, max_new_tokens=unit) for p in prompts]
        streams = [r.result(900) for r in reqs]
    wall = time.perf_counter() - t0

    _check(all(r.state == 'DONE' for r in reqs),
           'request states: %r' % [r.state for r in reqs])
    _check(all(len(s) == unit for s in streams),
           'stream lengths %r, want %d each' % ([len(s) for s in streams],
                                                unit))
    _check(all(0 <= t < cfg.vocab for s in streams for t in s),
           'a served token is outside the vocabulary')
    _check(streams[2] == streams[7],
           'identical prompts decoded differently:\n%r\n%r'
           % (streams[2], streams[7]))
    jit = dec.jit_cache_stats()
    _check(jit['compiled_segments'] == 3,
           'prefill, decode and the page copy program must each compile '
           'once: %r' % jit)

    # the repo's own reference: the plain predictor's full forward. The
    # first served token must be within a quarter of the logits' spread
    # of its argmax (chosen before the first chip run: the two paths
    # differ by matmul rounding, ~2^-8 of a logit's scale on a TPU,
    # while a wrong token sits ~4 spreads below the maximum of V draws)
    padded = np.zeros((1, cfg.max_len, 1), 'int64')
    padded[0, :len(prompts[0]), 0] = prompts[0]
    ref = np.asarray(pred.run([padded])[0])
    _check(ref.shape == (1, cfg.max_len, cfg.vocab),
           'reference logits shape %r' % (ref.shape,))
    ref = ref[0, len(prompts[0]) - 1]
    _check(np.isfinite(ref).all(), 'non-finite reference logits')
    gap = float(ref.max() - ref[streams[0][0]]) / float(ref.std())
    _check(gap <= 0.25, 'first served token %d sits %.3f logit-spreads '
           'below the reference argmax %d'
           % (streams[0][0], gap, int(ref.argmax())))

    stats = jax.devices()[0].memory_stats()
    row = {
        'config': 'L%d_D%d_H%d_F%d_T%d_V%d_fp32_paged_slots%d' % (
            cfg.layers, cfg.dim, cfg.heads, cfg.ffn, cfg.max_len,
            cfg.vocab, SLOTS),
        'requests': len(reqs), 'completed': len(streams),
        'prompt_lens': [len(p) for p in prompts], 'new_tokens': unit,
        'identical_prompts_identical_streams': True,
        'compiled_segments': jit['compiled_segments'],
        'ref_first_token_gap_spreads': round(gap, 4),
        'ref_first_token_is_argmax': bool(streams[0][0] == ref.argmax()),
        # the paged prefill program attends with the naive ops and the
        # decode program with the paged_attention kernel (neither is a
        # flash route); the reference forward is the flash kernel on
        # fp32 operands
        'flash_route': _flash_route(before),
        'pool': dec.pool_stats(),
        'memory_stats': _memory(stats),
        'info_wall_s': round(wall, 2),
    }
    _emit('serve', env, **row)
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1)
    ap.add_argument('--dry-run', action='store_true',
                    help='CPU walk-through at a tiny width (Pallas in '
                         'interpret mode); never a chip result')
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    if args.dry_run:
        # before jax is imported: the CPU backend, one virtual device
        # per chip asked for, and the interpret-mode kernels
        os.environ['JAX_PLATFORMS'] = 'cpu'
        os.environ['XLA_FLAGS'] = (
            os.environ.get('XLA_FLAGS', '')
            + ' --xla_force_host_platform_device_count=%d' % args.chips)
        os.environ['FLAGS_pallas_interpret'] = '1'

    env = phase_device(args)
    import jax
    cfg = _config(args.dry_run)
    devices = jax.devices()

    one = run_train(cfg, env, devices[:1], 'dp1')
    _release(devices[0])
    if args.chips == 4:
        four = run_train(cfg, env, devices[:4], 'dp4')
        # same seed, same global batch: the first-step losses differ by
        # rounding only. Stated tolerance: half a bf16 ulp of the loss.
        rel = abs(four['losses'][0] - one['losses'][0]) / one['losses'][0]
        _check(rel <= 2.0 ** -9,
               'first-step loss dp=4 %.5f vs one chip %.5f (rel %.2e)'
               % (four['losses'][0], one['losses'][0], rel))
        _emit('dp4_vs_dp1', env,
              first_loss_dp1=one['losses'][0],
              first_loss_dp4=four['losses'][0],
              first_loss_rel_diff=rel, tolerance=2.0 ** -9,
              flash_bh_per_device=four['flash_bh_per_device'],
              flash_bh_global=four['flash_bh_global'])
    else:
        phase_serve(cfg, env)

    print(json.dumps({'ok': True, 'device': {
        'platform': env['platform'], 'kind': env['device_kind'],
        'count': env['n_devices']}}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
