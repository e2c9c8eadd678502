"""Interleaved in-process flash-attention block autotune.

An earlier sweep ran one process per config and ±10-20% run-to-run
noise swallowed every difference (an honest null, PERF.md). The fix
that resolved it: keep EVERY arm in
ONE process, alternate arms across rounds, and difference in-jit N/2N
loops. This tool re-runs the (block_q, block_k) sweep that way.

    python tools/flash_autotune.py [--T 8192] [--bh 16] [--rounds 3]
        [--fwd-only] [--fwd-arm online|twopass] [--quick]

Prints per-config fwd+bwd ms a call (median over rounds) beside its
share of the chip's bf16 peak (peak_share below), so a real >5%
winner, if one exists, survives the noise floor. Populate
pallas/flash_attention._BLOCK_TABLE with any config that wins
consistently. The training cells' shape is `--T 2048 --bh 64` (4
sequences x 16 heads a chip, d=128, bf16, causal). --quick is the
tier-1 smoke: one tiny shape, one round, forward only, interpret mode
off-chip — it walks the harness, its numbers mean nothing.

--fwd-only times the forward alone (the round-5 fwd-table sweep mode,
now also the round-6 twopass mode); --fwd-arm forces a forward arm for
the whole sweep so the per-arm tables (_BLOCK_TABLE_FWD vs
_BLOCK_TABLE_FWD_TWOPASS, incl. the bk=1024 lane-parallel candidates)
stay honest — a config whose residency guard swaps the forced arm is
dropped from the ranking, same as a VMEM OOM.
"""
from __future__ import annotations

import argparse
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax
import jax.numpy as jnp


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def peak_share(ms, bh, T, d, fwd_only=True):
    """Percent of the v5e's 197 TFLOP/s that `ms` a call is for causal
    attention over bh heads of T x d, by the benchmark's own count
    (benchmarks/harness/costs.py: the two forward products, halved for
    the mask; forward + backward is three times that, the backward's
    recomputed QK^T not counted) — what `flash_roofline.train` divides
    by, so a tool's line and a cell's metric read on one scale."""
    bench = os.path.join(_ROOT, 'benchmarks')
    if bench not in sys.path:
        sys.path.append(bench)
    from harness import costs, peaks
    flops = costs.attn_flops_fwd({'n_embd': bh * d}, T)
    if not fwd_only:
        flops *= 3
    peak = peaks.peaks_of('TPU v5 lite')['bf16_flops']
    return flops / (ms * 1e-3) / peak * 100


def timed_step(flash, q, k, v, iters):
    def step(q, k, v):
        def loss(q, k, v):
            return flash._flash(q, k, v, True, 0.0884, False) \
                .astype(jnp.float32).sum()
        # grads wrt ALL inputs: argnums=0 alone would let XLA DCE the
        # dk/dv kernel out of the loop and the sweep would rank
        # configs on fwd+dq cost only
        gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        eps = jnp.bfloat16(1e-12)
        return (q + gq.astype(q.dtype) * eps,
                k + gk.astype(k.dtype) * eps,
                v + gv.astype(v.dtype) * eps)

    @jax.jit
    def loop(q, k, v):
        def body(c, _):
            return step(*c), None
        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None,
                                    length=iters)
        return q[0, 0, 0] + k[0, 0, 0] + v[0, 0, 0]
    return loop


def timed_fwd(flash, q, k, v, iters, interpret=False):
    def step(q, k, v):
        o, lse = flash._fwd(q, k, v, True, 0.0884, interpret)
        # fold BOTH outputs into the carry so neither the o nor the
        # lse side of the kernel can be DCE'd out of the loop; the
        # float32 lse is cast back down so the carry dtype is stable
        # across scan iterations
        eps = jnp.asarray(1e-12, q.dtype)
        return (q + (o.astype(jnp.float32) + lse)
                .astype(q.dtype) * eps, k, v)

    @jax.jit
    def loop(q, k, v):
        def body(c, _):
            return step(*c), None
        (q, k, v), _ = jax.lax.scan(body, (q, k, v), None,
                                    length=iters)
        return q[0, 0, 0]
    return loop


def measure(flash, q, k, v, iters=6, fwd_only=False, interpret=False,
            timed=None):
    """ms a step by differencing an N- and a 2N-step in-jit loop;
    `timed(flash, q, k, v, iters)` builds another loop than the two
    here (tools/flash_bwd_arms.py: the backward alone)."""
    if timed is None and fwd_only:
        def timed(flash, q, k, v, iters):
            return timed_fwd(flash, q, k, v, iters,
                             interpret=interpret)
    elif timed is None:
        # the fwd+bwd loop goes through _flash, which has no interpret
        # plumbing here — it is the chip-sweep path
        timed = timed_step
    l1 = timed(flash, q, k, v, iters)
    l2 = timed(flash, q, k, v, 2 * iters)
    np.asarray(l1(q, k, v)); np.asarray(l2(q, k, v))   # compile both
    t0 = time.perf_counter(); np.asarray(l1(q, k, v))
    t1 = time.perf_counter(); np.asarray(l2(q, k, v))
    t2 = time.perf_counter()
    return ((t2 - t1) - (t1 - t0)) / iters * 1e3  # ms per step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument('--T', type=int, default=8192)
    ap.add_argument('--d', type=int, default=128)
    ap.add_argument('--bh', type=int, default=16)
    ap.add_argument('--rounds', type=int, default=3)
    ap.add_argument('--blocks', type=int, nargs='+',
                    default=[256, 512, 1024])
    ap.add_argument('--fwd-only', action='store_true')
    ap.add_argument('--fwd-arm', default='',
                    choices=['', 'online', 'twopass'])
    ap.add_argument('--quick', action='store_true')
    args = ap.parse_args(argv)

    interpret = jax.default_backend() != 'tpu'
    if args.quick:
        args.T, args.bh, args.rounds = 256, 2, 1
        args.blocks, args.fwd_only = [128, 256], True
    elif interpret:
        raise SystemExit('the sweep needs a TPU backend (interpret-mode '
                         'timings rank the emulator); use --quick for '
                         'the harness smoke')

    import paddle_tpu as fluid
    from paddle_tpu.pallas import flash_attention as flash

    if args.fwd_arm:
        flash._FORCE_FWD_ARM = args.fwd_arm

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(args.bh, args.T, args.d), jnp.bfloat16)

    configs = [(bq, bk) for bq in args.blocks for bk in args.blocks
               if args.T % bq == 0 and args.T % bk == 0]
    results = {c: [] for c in configs}
    failed = set()
    for rnd in range(args.rounds):
        for cfg in configs:
            if cfg in failed:   # deterministic failures (VMEM OOM):
                continue        # don't re-pay compile every round
            fluid.flags.set_flags({'FLAGS_flash_block_q': cfg[0],
                                   'FLAGS_flash_block_k': cfg[1]})
            # block sizes bind at TRACE time via the flag — stale
            # traces must go
            flash._fwd.clear_cache()
            flash._bwd.clear_cache()
            try:
                ms = measure(flash, q, k, v,
                             iters=2 if args.quick else 6,
                             fwd_only=args.fwd_only,
                             interpret=interpret)
            except Exception as e:   # noqa: BLE001 — e.g. VMEM OOM
                failed.add(cfg)
                print('round %d  bq=%-5d bk=%-5d  FAILED (%.80s)'
                      % (rnd, cfg[0], cfg[1], str(e)), flush=True)
                continue
            if args.fwd_arm and flash._RESOLVED_FWD_ARM != args.fwd_arm:
                # the residency guard swapped the forced arm for this
                # block config — ranking the substitute would put an
                # online number in the twopass table
                failed.add(cfg)
                print('round %d  bq=%-5d bk=%-5d  SKIPPED (guard '
                      'dispatched %r)' % (rnd, cfg[0], cfg[1],
                                          flash._RESOLVED_FWD_ARM),
                      flush=True)
                continue
            results[cfg].append(ms)
            print('round %d  bq=%-5d bk=%-5d  %.2f ms'
                  % (rnd, cfg[0], cfg[1], ms), flush=True)
    flash._FORCE_FWD_ARM = ''
    fluid.flags.set_flags({'FLAGS_flash_block_q': 0,
                           'FLAGS_flash_block_k': 0})
    flash._fwd.clear_cache()
    flash._bwd.clear_cache()
    # drop configs with ANY failure: a transiently-failed arm would
    # otherwise rank on fewer samples, indistinguishable in the table
    configs = [c for c in configs if results[c] and c not in failed]
    if not configs:
        print('\nevery config failed — nothing to rank')
        return
    ranked = sorted(configs, key=lambda c: statistics.median(results[c]))
    base_cfg = (512, 512) if (512, 512) in configs else ranked[0]
    base = statistics.median(results[base_cfg])
    print('\nBH=%d T=%d d=%d bf16 causal, %s, ms a call'
          % (args.bh, args.T, args.d,
             'forward' if args.fwd_only else 'forward + backward'))
    print('| bq | bk | median ms | spread | vs %dx%d | %% of peak |'
          % base_cfg)
    print('|---|---|---|---|---|---|')
    for cfg in ranked:
        ms = results[cfg]
        med = statistics.median(ms)
        print('| %d | %d | %.3f | %.3f-%.3f | %+.1f%% | %.1f |'
              % (cfg[0], cfg[1], med, min(ms), max(ms),
                 (med / base - 1) * 100,
                 peak_share(med, args.bh, args.T, args.d,
                            args.fwd_only)))


if __name__ == '__main__':
    main()
